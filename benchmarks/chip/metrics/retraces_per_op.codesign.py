"""jit retraces per operation in the traced window: the program marks each
jaxpr trace with a ``repro.jit.retrace`` span on the thread that caused
it.  Zero markers read 0; a window with no ``repro.`` span at all reads
nothing, since then no marker could have been recorded."""

import sys

MARKER = "repro.jit.retrace"


def read(ctx):
    lo, hi = ctx.trace.window
    ours = [x for x in ctx.trace.host
            if x[0].startswith("repro.") and x[1] < hi and x[2] >= lo]
    if ours:
        retraces = sum(name == MARKER and s >= lo for name, s, _ in ours)
        return retraces / ctx.window.ops
    try:
        import repro.core.spans  # noqa: F401
    except ImportError:
        # a program from before its spans: nothing is counted, read as 0
        print("retraces_per_op: the program has no repro. spans; read 0",
              file=sys.stderr)
        return 0.0
    return None
