"""Device milliseconds per round spent in collectives (all-reduce,
all-gather, reduce-scatter, all-to-all, collective-permute and their async
halves), the mean over the cell's chips: the exchange between the fleet
mesh's shards of the cohort updates and of ``fed_reduce``'s partial sums."""
from trace_reduce import op_seconds

COLLECTIVE = (r"(?i)all-reduce|all-gather|reduce-scatter|all-to-all|"
              r"collective-permute|psum")


def read(run):
    secs, n = op_seconds(run.trace.ops(), COLLECTIVE)
    rounds = run.counters["rounds"]
    if not n or not rounds:
        return None
    return secs / run.chips / rounds * 1e3
