"""The whole round's share of the chips' bf16 peak: the operations the
traced rounds require (``flops.lr_round_flops``: local training forward and
backward, and the server's weighted reduction) over the traced window, the
cell's chips and the peak."""
from flops import lr_round_flops


def read(run):
    c = run.counters
    if not c["rounds"]:
        return None
    ops = c["rounds"] * lr_round_flops(c["devices"], c["records"], c["dim"],
                                       c["epochs"])
    return 100.0 * ops / (run.trace.window_s * run.chips
                          * run.peaks["bf16_flops_per_s"])
