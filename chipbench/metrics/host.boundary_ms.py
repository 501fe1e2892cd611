"""Mean host time per segment boundary in the wrapped control-loop calls
(plan draw, row mask, participation, cut mapping, segment dispatch,
clock walk, controller); eval and the loss fetch, which wait on the
device, are left out."""


def read(ctx):
    h = ctx.host_s
    return 1e3 * sum(h) / len(h) if h else None
