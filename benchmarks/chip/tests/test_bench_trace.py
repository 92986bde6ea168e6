"""The reduction from a profiler trace to the per-layer numbers."""

import json
from pathlib import Path

import pytest

import tracing

DATA = Path(__file__).resolve().parent / "data"


def hand_trace():
    """A 100 ns window; device 0 busy 10-30, 25-40 and 70-80; device 1
    busy 0-50; the host inside ``bench:pareto`` 40-60 and ``bench:popgen``
    60-75, with a jax event 62-68 under it."""
    return tracing.Trace(
        window=(0, 100),
        devices={
            "0": {"ops": [("fusion", 10, 30), ("congruence_kernel", 25, 40),
                          ("congruence_kernel", 70, 80)],
                  "modules": [("jit_a", 10, 40), ("jit_b", 70, 80)]},
            "1": {"ops": [("congruence_kernel", -5, 50)],
                  "modules": [("jit_a", -5, 50)]},
        },
        host=[(tracing.WINDOW, 0, 100), ("bench:pareto", 40, 60),
              ("bench:popgen", 60, 75), ("PjitFunction(f)", 62, 68)])


def test_union_and_gaps():
    ivs = [("a", 10, 30), ("b", 25, 40), ("c", 70, 80), ("d", 95, 120)]
    assert tracing.union_length(ivs, 0, 100) == 20 + 10 + 10 + 5
    assert tracing.gaps(ivs, 0, 100) == [(0, 10), (40, 70), (80, 95)]


def test_hand_trace_numbers():
    t = hand_trace()
    assert t.window_s == pytest.approx(100e-9)
    # device 0: 10-40 and 70-80 = 40 ns; device 1: 0-50 = 50 ns
    assert t.busy_s() == pytest.approx(45e-9)
    assert t.op_seconds(lambda n: "congruence" in n) == pytest.approx(
        (15 + 10 + 50) / 2 * 1e-9)
    assert t.op_seconds(lambda n: n == "nothing") is None
    assert t.modules_started() == pytest.approx(1.0)   # (2 + 0) / 2
    assert t.span_seconds(["pareto"]) == pytest.approx(20e-9)
    assert t.span_seconds(["pareto", "popgen"]) == pytest.approx(35e-9)
    assert t.span_seconds(["absent"]) is None


def test_hand_trace_breakdown():
    b = hand_trace().breakdown()
    ops = dict(b["device_ops"])
    assert ops["congruence_kernel"] == pytest.approx((25 + 50) / 2 * 1e-9)
    assert ops["fusion"] == pytest.approx(20 / 2 * 1e-9)
    # device 0 idles 0-10, 40-70 and 80-100: 40-60 inside pareto, 60-70
    # inside popgen, and the rest under no host event
    idle = dict(b["idle_gaps"])
    assert idle == pytest.approx({"no host event": 30e-9,
                                  "bench:pareto": 20e-9,
                                  "bench:popgen": 10e-9})


def test_json_round_trip():
    t = hand_trace()
    again = tracing.Trace.from_json(json.loads(json.dumps(t.to_json())))
    assert again == t


def test_recorded_tpu_trace():
    """The window of a traced zoo128.mega run on one TPU v5e, kept with
    the numbers its reduction gave when it was recorded."""
    import harness

    kernel = harness.Spec(None, harness.HERE).module(
        "metrics", "congruence_roofline").KERNEL
    case = json.loads((DATA / "mega_tpu_trace.json").read_text())
    t = tracing.Trace.from_json(case["trace"])
    want = case["expected"]
    assert t.window_s == pytest.approx(want["window_s"])
    assert t.busy_s() == pytest.approx(want["busy_s"])
    assert t.op_seconds(lambda n: bool(kernel.search(n))) == pytest.approx(
        want["kernel_s"])
    assert t.span_seconds(["pareto"]) == pytest.approx(want["pareto_s"])
    assert t.span_seconds(["popgen"]) == pytest.approx(want["popgen_s"])
    assert t.modules_started() == pytest.approx(want["modules"])


def test_live_cpu_trace_finds_window_and_spans():
    """Recording and loading a real (CPU) profiler trace: the window span
    and a wrapped function's span are found on the same thread."""
    import sys

    import jax
    import jax.numpy as jnp

    mod = sys.modules[__name__]
    original = mod.work
    f = jax.jit(lambda x: jnp.sin(x) * 2)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with tracing.spans_installed({f"{__name__}:work": "work"}), \
            tracing.Recording() as rec:
        with jax.profiler.TraceAnnotation(tracing.WINDOW):
            for _ in range(3):
                mod.work(f, x)
    t = rec.trace
    assert t.window_s > 0
    assert 0 < t.span_seconds(["work"]) <= t.window_s
    assert mod.work is original              # the wrapper was taken off


def work(f, x):
    return f(x).block_until_ready()
