"""Apps x variants scored per second over the whole window, population to
fronts: every operation's time counts, its cells once."""


def read(ctx):
    cells = ctx.window.counts.get("cells")
    return None if cells is None else cells / ctx.window.seconds
