"""The fused clip+SGD kernel's share of its roofline.

Every round reads each client's parameters and gradients and writes its
parameters once, in float32: 12 bytes per parameter per client.  The
least time is those bytes over HBM bandwidth; the measured time is the
device time of the kernel's Pallas calls in the traced window.
"""
from chipbench import trace as TR
from chipbench.counts import cnn

def is_update(op):
    """The Pallas calls of `kernels/clip_sgd.py` (named ``clip_sgd.<n>``
    after the jitted function that makes them)."""
    return op.category == "custom-call" and op.name.startswith("clip_sgd")


def read(ctx):
    s = ctx.summary
    if s is None or ctx.peaks is None:
        return None
    lo, hi = ctx.trace.window()
    busy = sum(TR.op_time(ops, is_update, lo, hi)
               for ops in ctx.trace.ops.values())
    if busy <= 0:
        return None
    n = ctx.traffic["fleet"]["n"]
    rounds = ctx.traffic["trace_rounds"]
    least = rounds * 12 * n * cnn.param_count(ctx.cfg) \
        / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / busy
