"""Attention cores' share of their roofline.

The least time of every attention-core pass of the traced window (each
client's forward and backward passes over its real rows with its own
weights, and each segment's eval forward of the test set: the encoder's
self-attention, the decoder's self- and cross-attention), each bound by
the larger of its operations over the bf16 peak and its bytes over HBM
bandwidth (`counts.whisper.attention_passes`), over the device time,
summed over the chips, of the window's ops under the program's
``attention`` scope.  None where no op carries the scope.
"""
import re

from chipbench import program_trace as PT
from chipbench import trace as TR
from chipbench.counts import whisper

# the scope, bare or inside a transformation's name (``jvp(attention)``)
SCOPE = re.compile(r"^(?:[\w]+\()*attention\)*$")


def under_attention(op) -> bool:
    return any(SCOPE.match(c) for c in op.path.split("/")[:-1])


def read(ctx):
    pt = ctx.program
    if pt is None or ctx.peaks is None:
        return None
    lo, hi = pt.window
    busy = sum(TR.length(TR.clip(((o.start, o.end) for o in PT.work(ops)
                                  if under_attention(o)), lo, hi))
               for ops in pt.ops.values())
    if busy <= 0:
        return None
    rounds = ctx.traffic["trace_rounds"]
    evals = rounds // ctx.traffic["eval_every"]
    peak, bw = ctx.peaks["bf16_flops"], ctx.peaks["hbm_bytes_per_s"]
    least = rounds * whisper.roofline_seconds(whisper.attention_passes(
        ctx.cfg, ctx.traffic, [int(x) for x in ctx.b], train=True), peak, bw)
    least += evals * whisper.roofline_seconds(whisper.attention_passes(
        ctx.cfg, ctx.traffic, [ctx.traffic["n_test"]], train=False), peak, bw)
    return 100.0 * least / busy
