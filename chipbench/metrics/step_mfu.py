"""Model operations of the traced window over the chips' bf16 peak.

Operations: every client's real rows (padded rows left out) through the
forward and backward passes of each round, and each segment's eval
forward of the test set, counted from layer shapes by the family's
counts (``counts/<family>.py``, `cells.family`).  bf16 is the peak
because a float32 matmul or convolution at the default precision makes
one bf16 pass.
"""
from chipbench import cells


def read(ctx):
    s = ctx.summary
    if s is None or ctx.peaks is None:
        return None
    counts = cells.family(ctx.cfg).counts
    rounds = ctx.traffic["trace_rounds"]
    evals = rounds // ctx.traffic["eval_every"]
    flops = (rounds * int(ctx.b.sum()) * counts.train_flops(ctx.cfg,
                                                            ctx.traffic)
             + evals * ctx.traffic["n_test"]
             * counts.forward_flops(ctx.cfg, ctx.traffic))
    peak = ctx.peaks["bf16_flops"] * s["window_s"] * s["chips"]
    return 100.0 * flops / peak
