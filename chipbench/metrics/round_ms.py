"""Wall time per round over the whole window, boundaries included."""


def read(ctx):
    w = ctx.window
    return 1e3 * w["wall_s"] / w["rounds"] if w["rounds"] else None
