"""The clip+SGD update's share of its roofline.

Every round reads each client's parameters and gradients and writes its
parameters once, in float32: 12 bytes per parameter per client, the
parameters counted by the family's counts (`cells.family`).  The least
time is those bytes over HBM bandwidth; the measured time is the device
time, summed over the chips, of the window's ops under the program's
``update`` scope (`program_trace.UPDATE`): the inline update, or the
Pallas kernel of `kernels/clip_sgd.py` with the relayouts XLA puts
around it where a spec pins ``update_impl``.  The name is the kernel's,
whose place the inline update took.
"""
from chipbench import cells
from chipbench import program_trace as PT


def read(ctx):
    pt = ctx.program
    if pt is None or ctx.peaks is None:
        return None
    busy = PT.scope_seconds(pt, PT.UPDATE)
    if busy <= 0:
        return None
    n = ctx.traffic["fleet"]["n"]
    rounds = ctx.traffic["trace_rounds"]
    least = rounds * 12 * n * cells.family(ctx.cfg).counts.param_count(
        ctx.cfg) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / busy
