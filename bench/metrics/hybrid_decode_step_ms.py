"""Device milliseconds per hybrid ``arena_decode`` program: the mean
duration of the compiled-program executions in the trace that run the
``ssd_decode`` kernel (no other serving program does)."""
from trace_reduce import containing, matching

KERNEL = r"^%?ssd_decode(\.\d+)?( =|$)"  # see ssd_decode_roofline


def read(run):
    t = run.trace
    decode = containing(t.programs(), matching(t.ops(), KERNEL))
    if not decode:
        return None
    return sum(d for _, _, d in decode) / len(decode) * 1e-6
