"""The program's own spans and jit markers (``repro.core.spans``), and the
per-layer metrics that read them."""

import sys
import types

import pytest

import harness
import tracing

#: Every span the program opens on the window's thread in a streamed
#: mega-sweep and a descent.  ``repro.jit.cache_hit`` needs a persistent
#: compilation cache and is left out here.
SPANS = {"repro.sweep", "repro.shard_sweep", "repro.shard", "repro.popgen",
         "repro.stage", "repro.fetch", "repro.reduce", "repro.pareto",
         "repro.rescore", "repro.codesign", "repro.descent.step",
         "repro.descent.sync", "repro.jit.retrace", "repro.jit.compile"}


def _profiles(k=3):
    from repro.core import WorkloadProfile

    return [WorkloadProfile(
        name=f"app{i}", flops=2e14 * (i + 1), hbm_bytes=1.5e11 * (1 + i),
        collective_bytes={"all-reduce": 2e10 * (i + 1)}, num_devices=256,
        model_flops=5e16) for i in range(k)]


def _mega(n):
    from repro.core.sweep import shard_sweep
    from repro.launch.mesh import make_variant_mesh

    return shard_sweep(_profiles(), n=n, num_shards=2, stream=True,
                       backend="pallas", mesh=make_variant_mesh(1))


def _solve():
    from repro.core import VARIANTS, grad_codesign
    from repro.core.sweep import MachineBatch

    return grad_codesign(_profiles(), MachineBatch.from_models(VARIANTS),
                         steps=3)


def _recorded(*ops):
    import jax

    with tracing.Recording() as rec:
        with jax.profiler.TraceAnnotation(tracing.WINDOW):
            for op in ops:
                op()
    return rec.trace


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_live_cpu_trace_has_every_program_span():
    """A tiny mega-sweep (2 shards, pallas interpreted) and a 3-step solve:
    every span is on the window's thread, nested as the layers are."""
    t = _recorded(lambda: _mega(256), _solve)
    names = {name for name, _, _ in t.host}
    assert SPANS <= names, SPANS - names
    by = {n: [x for x in t.host if x[0] == n] for n in SPANS}
    assert all(any(_inside(s, c) for c in by["repro.codesign"])
               for s in by["repro.descent.step"])
    assert len(by["repro.descent.step"]) == 3
    assert all(any(_inside(f, s) for f in by["repro.fetch"])
               for s in by["repro.shard"])
    assert len(by["repro.shard"]) == 2
    ours = [x for x in t.host if x[0].startswith("repro.")]
    covered = tracing.union_length(ours, *t.window) / 1e9
    assert covered >= 0.9 * t.window_s


def test_span_count_does_not_grow_with_the_population():
    """The spans wrap calls, shards and steps, never a per-variant loop:
    at a fixed shard count, twice the variants open as many spans."""
    counts = []
    for n in (256, 512):
        _mega(n)                                   # compile outside
        t = _recorded(lambda: _mega(n))
        counts.append(sum(name.startswith("repro.")
                          and not name.startswith("repro.jit.")
                          for name, _, _ in t.host))
    assert counts[0] == counts[1] > 0


# --------------------------------------------------------------------------- #
# The readers, on hand-made traces
# --------------------------------------------------------------------------- #


def _metric(name):
    return harness.Spec(None, harness.HERE).module("metrics", name)


def _ctx(host, ops=2, steps=4):
    trace = tracing.Trace(window=(0, 1000), devices={},
                          host=[(tracing.WINDOW, 0, 1000)] + host)
    window = types.SimpleNamespace(seconds=1e-6, ops=ops,
                                   counts={"descent_steps": float(steps)})
    return types.SimpleNamespace(trace=trace, window=window)


SWEEP_HOST = [("repro.shard_sweep", 0, 990), ("repro.shard", 0, 400),
              ("repro.fetch", 100, 300), ("repro.fetch", 250, 350),
              ("repro.shard", 400, 900), ("repro.fetch", 500, 600),
              ("repro.fetch", 1100, 1200)]                  # after the window
DESCENT_HOST = [("repro.codesign", 0, 1000), ("repro.descent.step", 10, 110),
                ("repro.descent.step", 110, 310),
                ("repro.descent.sync", 300, 310)]
MARKERS = [("repro.jit.retrace", 50, 50), ("repro.jit.compile", 60, 70),
           ("repro.jit.retrace", 700, 701), ("repro.jit.retrace", 1500, 1501)]


def test_fetch_share_reads_the_fetch_spans():
    read = _metric("fetch_share").read
    assert read(_ctx(SWEEP_HOST)) == pytest.approx((250 + 100) / 1000)
    assert read(_ctx([x for x in SWEEP_HOST
                      if x[0] != "repro.fetch"])) is None


def test_descent_step_ms_reads_the_step_spans():
    read = _metric("descent_step_ms").read
    # 300 ns of steps over 4 steps
    assert read(_ctx(DESCENT_HOST, steps=4)) == pytest.approx(300e-6 / 4)
    assert read(_ctx(DESCENT_HOST[:1])) is None


@pytest.mark.parametrize("name", ["retraces_per_op.sweep",
                                  "retraces_per_op.codesign"])
def test_retraces_per_op_counts_the_markers(name):
    read = _metric(name).read
    assert read(_ctx(SWEEP_HOST + MARKERS, ops=2)) == pytest.approx(2 / 2)
    assert read(_ctx(DESCENT_HOST, ops=3)) == 0.0        # no marker
    assert read(_ctx([("PjitFunction(f)", 10, 20)])) is None   # no span


@pytest.mark.parametrize("name", ["fetch_share", "descent_step_ms",
                                  "retraces_per_op.sweep",
                                  "retraces_per_op.codesign"])
def test_program_without_spans_reads_zero(monkeypatch, name):
    """A program from before ``repro.core.spans`` opens none of these
    spans; its traced run reads 0 instead of failing."""
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)
    assert _metric(name).read(_ctx([("PjitFunction(f)", 10, 20)])) == 0.0
