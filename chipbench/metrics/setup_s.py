"""Process start to window start: imports, Session build, weights and
data, the set-up rounds and every compile or cache load."""


def read(ctx):
    return ctx.setup_s
