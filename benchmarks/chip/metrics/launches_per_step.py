"""Device program executions per co-design descent step in the traced
window, counted from the trace."""


def read(ctx):
    steps = ctx.window.counts.get("descent_steps")
    launches = ctx.trace.modules_started()
    if not steps or launches is None:
        return None
    return launches / steps
