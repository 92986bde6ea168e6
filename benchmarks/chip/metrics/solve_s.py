"""Seconds per co-design solve: the whole window over the solves in it."""


def read(ctx):
    solves = ctx.window.counts.get("solves")
    return None if not solves else ctx.window.seconds / solves
