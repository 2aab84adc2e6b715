"""``fed_reduce``'s share of its roofline: the least time the chip could
take for the weighted row-sums of the traced rounds (the larger of their
bytes over HBM bandwidth and their operations over the bf16 peak; the bytes
bound it) over the device time of the kernel's events in the trace.

The kernel's events carry the name of its HLO custom call, which XLA takes
from the innermost jit around the ``pallas_call`` (seen in a v5e trace:
``%decode_attention.6``, ``%fed_reduce_pallas.3``): ``fed_reduce_pallas.<n>``
(the jitted ``fed_reduce_pallas``), the only instructions of that name in
the streaming partial reduce compiled for a v5e."""
from flops import fed_reduce_cost
from trace_reduce import op_seconds

KERNEL = r"^%?fed_reduce_pallas(\.\d+)?( =|$)"


def read(run):
    c = run.counters
    secs, n = op_seconds(run.trace.ops(), KERNEL)
    if not n or not c["rounds"]:
        return None
    flops = nbytes = 0.0
    for rows, size, itemsize, scaled in c["fed_reduce_calls"]:
        f, b = fed_reduce_cost(rows, size, itemsize, scaled=scaled)
        flops, nbytes = flops + f, nbytes + b
    p = run.peaks
    least = c["rounds"] * max(nbytes / p["hbm_bytes_per_s"],
                              flops / p["bf16_flops_per_s"])
    return 100.0 * least / secs
