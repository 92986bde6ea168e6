"""Share of the traced window the host spends generating the machine
population (each streamed shard, the survivors' rows, a materialized
population)."""

SPANS = {
    "repro.core.sweep:PopulationStream.batch": "popgen",
    "repro.core.sweep:PopulationStream.take": "popgen",
    "repro.core.sweep:_population": "popgen",
}


def read(ctx):
    t = ctx.trace.span_seconds(["popgen"])
    return None if t is None else t / ctx.trace.window_s
