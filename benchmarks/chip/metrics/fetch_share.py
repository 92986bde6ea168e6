"""Share of the traced window the host spends copying kernel results from
the device: the union of the program's ``repro.fetch`` spans (each
device-to-host copy of a kernel result) over the window."""

import sys

import tracing

SPAN = "repro.fetch"


def read(ctx):
    spans = [x for x in ctx.trace.host if x[0] == SPAN]
    t = tracing.union_length(spans, *ctx.trace.window)
    if t:
        return t / 1e9 / ctx.trace.window_s
    try:
        import repro.core.spans  # noqa: F401
    except ImportError:
        # a program from before its spans: nothing is timed, read as 0
        print(f"fetch_share: the program has no {SPAN} spans; read 0",
              file=sys.stderr)
        return 0.0
    return None
