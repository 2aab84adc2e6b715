"""``decode_attention``'s share of its roofline: the least time the chip
could take for the traced decode steps' attention (K and V read up to each
active slot's length, q read and o written; bytes bound it), in every
layer, over the device time of the kernel's events in the trace.

The kernel's events carry the name of its HLO custom call, which XLA takes
from the innermost jit around the ``pallas_call`` (seen in a v5e trace:
``%decode_attention.6``, ``%fed_reduce_pallas.3``): ``decode_attention.<n>``
(``jit(decode_attention)`` in ``kernels/decode_attention/ops.py``), the only
instruction of that name in the decode step compiled for a v5e."""
from flops import decode_attention_cost
from trace_reduce import op_seconds

KERNEL = r"^%?decode_attention(\.\d+)?( =|$)"


def read(run):
    m = run.config["model"]
    secs, n = op_seconds(run.trace.ops(), KERNEL)
    if not n:
        return None
    flops = nbytes = 0.0
    for _, _, _, _, lengths in run.counters["steps"]:
        f, b = decode_attention_cost(lengths, m["num_heads"],
                                     m["num_kv_heads"], m["head_dim"], 2)
        flops, nbytes = flops + f, nbytes + b
    p = run.peaks
    least = m["num_layers"] * max(nbytes / p["hbm_bytes_per_s"],
                                  flops / p["bf16_flops_per_s"])
    return 100.0 * least / secs
