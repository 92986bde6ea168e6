"""Share of the traced window in which no op ran on the device, averaged
over the chips (co-design cells)."""


def read(ctx):
    busy = ctx.trace.busy_s()
    return None if busy is None else 1.0 - busy / ctx.trace.window_s
