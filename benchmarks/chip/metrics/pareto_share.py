"""Share of the traced window the host spends extracting Pareto fronts
(the per-shard pre-filter and the final fronts, 2-D and 3-D)."""

SPANS = {
    "repro.core.sweep:pareto_front_indices": "pareto",
    "repro.core.sweep:pareto_front_indices_3d": "pareto",
}


def read(ctx):
    t = ctx.trace.span_seconds(["pareto"])
    return None if t is None else t / ctx.trace.window_s
