"""Convolutions' share of their roofline.

The least time of every convolution pass of the traced window (each
client's forward, input-gradient and weight-gradient passes on its real
rows with its own weights, and each segment's eval forward of the test
set), each pass bound by the larger of its operations over the bf16
peak and its minimal bytes over HBM bandwidth, over the device time of
the ops that run convolutions: XLA convolution ops and fusions, or the
Pallas batched matmul (im2col padding not counted as work).
"""
from chipbench import trace as TR
from chipbench.counts import cnn

def is_conv(op):
    """XLA's convolutions and convolution fusions, and the Pallas batched
    matmul of `kernels/batched_conv.py` (its calls take their names from
    the jitted ``batched_conv`` that makes them)."""
    if op.category in ("convolution", "convolution fusion"):
        return True
    return op.category == "custom-call" and "batched_conv" in op.name


def read(ctx):
    s = ctx.summary
    if s is None or ctx.peaks is None:
        return None
    lo, hi = ctx.trace.window()
    busy = sum(TR.op_time(ops, is_conv, lo, hi)
               for ops in ctx.trace.ops.values())
    if busy <= 0:
        return None
    rounds = ctx.traffic["trace_rounds"]
    evals = rounds // ctx.traffic["eval_every"]
    batches = [int(x) for x in ctx.b]
    least = rounds * cnn.roofline_seconds(
        cnn.conv_passes(ctx.cfg, batches, train=True),
        ctx.peaks["bf16_flops"], ctx.peaks["hbm_bytes_per_s"])
    least += evals * cnn.roofline_seconds(
        cnn.conv_passes(ctx.cfg, [ctx.traffic["n_test"]], train=False),
        ctx.peaks["bf16_flops"], ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / busy
