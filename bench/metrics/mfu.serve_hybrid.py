"""The whole hybrid serving step's share of the chip's bf16 peak: the
forward operations every token served in the traced window requires
(``flops_hybrid.hybrid_token_flops`` at its own context length: 9 Mamba2
and 1 attention layer, the router, 10 x 9/72 held-expert SwiGLUs and the
shared expert a layer, the unembedding; a prompt's tokens each at their
position) over the window and the peak.  Padding rows of the prefill and
work for idle slots do not count."""
from flops_hybrid import hybrid_token_flops


def read(run):
    m, c = run.config["model"], run.counters
    prompt = run.traffic["prompt_len"]
    per_prompt = sum(hybrid_token_flops(m, p) for p in range(1, prompt + 1))
    ops = 0.0
    for _, _, _, admitted, lengths in c["steps"]:
        ops += sum(hybrid_token_flops(m, n) for n in lengths)
        ops += admitted * per_prompt
    if not ops:
        return None
    return 100.0 * ops / (run.trace.window_s * run.peaks["bf16_flops_per_s"])
