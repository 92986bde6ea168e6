"""repro.core.spans: named host spans and the jit counters."""

import os
import subprocess
import sys
import textwrap

import pytest

from repro.core import spans

SRC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")


def test_new_jit_bumps_retrace_and_cached_call_does_not():
    import jax
    import jax.numpy as jnp

    x = jnp.arange(7.0)
    before = spans.counters()
    f = jax.jit(lambda v: jnp.cos(v) * 3.0 + 1.0)
    f(x).block_until_ready()
    traced = spans.counters()
    assert traced["retrace"] > before["retrace"]
    assert traced["compile"] > before["compile"]
    f(x).block_until_ready()
    assert spans.counters() == traced
    f(jnp.arange(9.0)).block_until_ready()     # a new shape traces again
    assert spans.counters()["retrace"] > traced["retrace"]


def test_cache_hit_event_is_counted():
    import jax

    before = spans.counters()["cache_hit"]
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    jax.monitoring.record_event("/jax/some/other_event")
    assert spans.counters()["cache_hit"] == before + 1


def test_counters_are_a_copy():
    spans.counters()["retrace"] = -1
    assert spans.counters()["retrace"] >= 0


def test_names_built_counts_only_the_rows_whose_names_are_read():
    from repro.core.sweep import ParamSpace, PopulationStream

    stream = PopulationStream(ParamSpace.default(), 5000, seed=3)
    before = spans.counters()["names_built"]
    shard = stream.batch(1000, 3000)
    part = shard.slice(0, 700)
    assert spans.counters()["names_built"] == before
    assert len(part.names) == 700
    assert spans.counters()["names_built"] == before + 700
    assert part.names[0] == "sweep-01000"
    assert spans.counters()["names_built"] == before + 700


def test_span_and_spanned_work_before_jax_is_imported():
    """The numpy-only paths use spans without loading jax."""
    code = textwrap.dedent("""
        import sys
        from repro.core import spans

        @spans.spanned("pareto")
        def f(x):
            return x + 1

        with spans.span("sweep"):
            assert f(1) == 2
        assert spans.counters() == {"retrace": 0, "compile": 0,
                                    "cache_hit": 0, "names_built": 0}
        assert f.__name__ == "f"
        assert "jax" not in sys.modules
        print("ok")
    """)
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ok"


@pytest.mark.parametrize("name", ["sweep", "descent.step"])
def test_span_names_the_work_in_a_trace(tmp_path, name):
    import glob

    import jax

    with jax.profiler.trace(str(tmp_path)):
        with spans.span(name):
            pass
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {e.name for plane in jax.profiler.ProfileData.from_file(
        path).planes for line in plane.lines for e in line.events}
    assert f"repro.{name}" in names
