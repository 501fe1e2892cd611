"""Model operations of the traced window over the chips' bf16 peak.

Operations: every client's real rows (padded rows left out) through the
forward and backward passes of each round, and each segment's eval
forward of the test set, counted from layer shapes
(`chipbench/counts/cnn.py`).  bf16 is the peak because a float32
convolution at the default precision makes one bf16 pass.
"""
from chipbench.counts import cnn


def read(ctx):
    s = ctx.summary
    if s is None or ctx.peaks is None:
        return None
    rounds = ctx.traffic["trace_rounds"]
    evals = rounds // ctx.traffic["eval_every"]
    flops = (rounds * int(ctx.b.sum()) * cnn.train_flops(ctx.cfg)
             + evals * ctx.traffic["n_test"] * cnn.forward_flops(ctx.cfg))
    peak = ctx.peaks["bf16_flops"] * s["window_s"] * s["chips"]
    return 100.0 * flops / peak
