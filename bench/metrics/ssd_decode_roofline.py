"""``ssd_decode``'s share of its roofline: the least time the chip could
take for the traced decode steps' SSM state update (each active slot's f32
state read and written once in every Mamba layer, with x, dt, B and C in
and y out; bytes bound it), over the device time of the kernel's events in
the trace.

The kernel's events carry the name of its HLO custom call, which XLA takes
from the innermost jit around the ``pallas_call``: ``ssd_decode.<n>``
(``jit(ssd_decode)`` in ``kernels/ssd_scan/ops.py``; the first in a
program is ``ssd_decode`` bare)."""
from flops_hybrid import ssd_decode_cost
from trace_reduce import op_seconds

KERNEL = r"^%?ssd_decode(\.\d+)?( =|$)"


def read(run):
    secs, n = op_seconds(run.trace.ops(), KERNEL)
    if not n:
        return None
    flops = nbytes = 0.0
    for _, _, _, _, lengths in run.counters["steps"]:
        f, b = ssd_decode_cost(len(lengths), run.config["model"])
        flops, nbytes = flops + f, nbytes + b
    p = run.peaks
    least = max(nbytes / p["hbm_bytes_per_s"], flops / p["bf16_flops_per_s"])
    return 100.0 * least / secs
