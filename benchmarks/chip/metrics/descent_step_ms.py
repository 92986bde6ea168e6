"""Milliseconds per co-design descent step: the union of the program's
``repro.descent.step`` spans over the traced window, over the steps the
window's solves took."""

import sys

import tracing

SPAN = "repro.descent.step"


def read(ctx):
    steps = ctx.window.counts.get("descent_steps")
    spans = [x for x in ctx.trace.host if x[0] == SPAN]
    t = tracing.union_length(spans, *ctx.trace.window)
    if steps and t:
        return t / 1e6 / steps
    try:
        import repro.core.spans  # noqa: F401
    except ImportError:
        # a program from before its spans: nothing is timed, read as 0
        print(f"descent_step_ms: the program has no {SPAN} spans; read 0",
              file=sys.stderr)
        return 0.0
    return None
