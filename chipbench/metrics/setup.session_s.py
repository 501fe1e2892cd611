"""Host time to construct the `Session` (model init, data, simulator)."""


def read(ctx):
    return ctx.session_s
