"""The whole serving step's share of the chip's bf16 peak: the forward
operations every token served in the traced window requires
(``flops.moe_token_flops`` at its own context length: granite's active
parameters, 8 of 40 experts, the router, the unembedding and attention over
the cache; a prompt's tokens each at their position) over the window and the
peak.  Padding rows of the prefill and work for idle slots do not count."""
from flops import moe_token_flops


def read(run):
    m, c = run.config["model"], run.counters
    prompt = run.traffic["prompt_len"]
    ops = 0.0
    for _, _, _, admitted, lengths in c["steps"]:
        ops += sum(moe_token_flops(m, n) for n in lengths)
        ops += admitted * sum(moe_token_flops(m, p)
                              for p in range(1, prompt + 1))
    if not ops:
        return None
    return 100.0 * ops / (run.trace.window_s * run.peaks["bf16_flops_per_s"])
