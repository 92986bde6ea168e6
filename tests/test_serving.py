"""Co-design service: equality pins, cache accounting, queue semantics,
and the continuous-batching engine regressions.

The load-bearing properties (the ISSUE acceptance gates):
  * micro-batched concurrent sweeps are BYTE-IDENTICAL to per-request
    ``run_sweep`` (the kernels are app-rowwise independent; admission
    concatenates suites, scoring runs once, results scatter back);
  * byte-identical repeat requests hit the result memo (same object out,
    cache accounting visible) -- cached frontier == cold frontier;
  * overload rejects at submit (429-style), timeouts expire jobs, and
    cancellation lands between mega-sweep shards -- never a hang;
  * every result type renders through the one protocol
    (``markdown(top_k)`` / ``to_json(top_k)``);
  * ``BatchedEngine`` regressions: empty-prompt admission and staggered
    admissions with per-slot KV positions.
"""

import json
import threading
import time

import numpy as np
import pytest

from repro.core import CodesignSpec, VARIANTS, WorkloadProfile, run_sweep
from repro.core.frontier import frontier_codesign
from repro.core.sweep import MachineBatch, ParamSpace
from repro.serving.codesign_service import (
    CANCELLED,
    DONE,
    TIMEOUT,
    CodesignRequest,
    CodesignService,
    JobCancelled,
    JobTimeout,
    ServiceOverloadError,
    render_result,
)
from test_sweep import random_profiles


def suite(tag: str, k: int = 2):
    """Deterministic per-tag synthetic suite (distinct across tags)."""
    base = abs(hash(tag)) % 7 + 1
    return [WorkloadProfile(
        name=f"{tag}/app{i}", flops=2e14 * (base + i),
        hbm_bytes=1.5e11 * (1 + 0.4 * i),
        collective_bytes={"all-reduce": 2e10 * (i + 1)},
        num_devices=256, model_flops=5e16) for i in range(k)]


SPEC32 = CodesignSpec(n=32, seed=0)


def sweep_req(tag, k=2, **kw):
    return CodesignRequest(kind="sweep", profiles=suite(tag, k),
                           spec=SPEC32, **kw)


def jax_sweep_req(tag, k=2):
    """A sweep on the jax backend, whose kernels trace per shape."""
    return CodesignRequest(kind="sweep", profiles=suite(tag, k),
                           spec=CodesignSpec(n=32, seed=0, backend="jax"))


# --------------------------------------------------------------------------- #
# Micro-batching equality pins
# --------------------------------------------------------------------------- #


def assert_sweep_equal(a, b):
    assert a.apps == b.apps
    assert a.machines.names == b.machines.names
    np.testing.assert_array_equal(a.beta, b.beta)
    np.testing.assert_array_equal(a.gamma, b.gamma)
    np.testing.assert_array_equal(a.aggregate, b.aggregate)
    for key in b.scores:
        np.testing.assert_array_equal(a.scores[key], b.scores[key])
    for key in b.alphas:
        np.testing.assert_array_equal(a.alphas[key], b.alphas[key])


def test_batched_sweeps_byte_identical_to_direct():
    """THE tentpole pin: three concurrent suites ride one SoA pass and
    each scattered result equals its solo run_sweep bit for bit."""
    svc = CodesignService(auto_start=False)
    tags = ("alpha", "bravo", "charlie")
    jids = [svc.submit(sweep_req(t, k=1 + i)) for i, t in enumerate(tags)]
    svc.drain()
    assert svc.stats["batched_groups"] == 1
    assert svc.stats["batched_requests"] == len(tags)
    for i, (t, jid) in enumerate(zip(tags, jids)):
        got = svc.result(jid, timeout=5)
        direct = run_sweep(suite(t, k=1 + i), n=32, seed=0)
        assert_sweep_equal(got, direct)


def test_batched_sweeps_resolve_beta_per_request():
    """Distinct explicit beta targets don't block batching: each request's
    per-app beta vector is resolved independently and concatenated."""
    svc = CodesignService(auto_start=False)
    j1 = svc.submit(CodesignRequest(
        kind="sweep", profiles=suite("x"), spec=CodesignSpec(n=32, beta=0.5)))
    j2 = svc.submit(CodesignRequest(
        kind="sweep", profiles=suite("y"), spec=CodesignSpec(n=32, beta=2.0)))
    svc.drain()
    assert svc.stats["batched_requests"] == 2
    assert_sweep_equal(svc.result(j1, timeout=5),
                       run_sweep(suite("x"), n=32, beta=0.5))
    assert_sweep_equal(svc.result(j2, timeout=5),
                       run_sweep(suite("y"), n=32, beta=2.0))


def test_incompatible_sweeps_do_not_batch():
    svc = CodesignService(auto_start=False)
    svc.submit(sweep_req("p"))
    svc.submit(CodesignRequest(kind="sweep", profiles=suite("q"),
                               spec=CodesignSpec(n=64)))   # different pop
    svc.drain()
    assert svc.stats["batched_groups"] == 0
    assert svc.stats["pop_misses"] == 2


def test_single_sweep_matches_direct_and_population_cache_hits():
    svc = CodesignService(auto_start=False)
    j1 = svc.submit(jax_sweep_req("solo"))
    svc.drain()
    assert svc.stats["pop_misses"] == 1
    retraces = svc.stats["retrace"]
    j2 = svc.submit(jax_sweep_req("other", k=3))  # same space/n/seed, new A
    svc.drain()
    assert svc.stats["pop_hits"] == 1          # population regenerated 0x
    assert svc.stats["retrace"] > retraces     # different A -> new shapes
    assert_sweep_equal(svc.result(j1, timeout=5),
                       run_sweep(suite("solo"), n=32, seed=0, backend="jax"))
    assert_sweep_equal(svc.result(j2, timeout=5),
                       run_sweep(suite("other", 3), n=32, seed=0,
                                 backend="jax"))


# --------------------------------------------------------------------------- #
# Result memo + jit accounting
# --------------------------------------------------------------------------- #


def test_repeat_request_hits_memo_and_is_same_result():
    svc = CodesignService(auto_start=False)
    j1 = svc.submit(sweep_req("memo"))
    svc.drain()
    assert svc.stats["memo_hits"] == 0
    j2 = svc.submit(sweep_req("memo"))
    svc.drain()
    assert svc.stats["memo_hits"] == 1
    assert svc.result(j2, timeout=5) is svc.result(j1, timeout=5)
    assert svc.poll(j2)["cache"] == "memo"
    assert svc.poll(j1)["cache"] is None


def test_cached_repeat_is_measurably_cheaper():
    """The cache economics pin: a memo'd repeat skips population build,
    beta resolution, and scoring entirely -- orders of magnitude faster
    than the cold run that populated it."""
    svc = CodesignService(auto_start=False)
    svc.submit(sweep_req("econ", k=3))
    t0 = time.perf_counter()
    svc.drain()
    cold_s = time.perf_counter() - t0
    svc.submit(sweep_req("econ", k=3))
    t0 = time.perf_counter()
    svc.drain()
    cached_s = time.perf_counter() - t0
    assert cached_s < cold_s  # measurably cheaper (typically >100x)


def test_cached_frontier_equals_cold_frontier():
    """Frontier memo pin: repeat frontier request returns the identical
    result object the cold run produced (byte-identical by identity)."""
    svc = CodesignService(auto_start=False)
    spec = CodesignSpec(budgets=[0.6, 1.2], steps=4, refine_steps=2)
    req = lambda: CodesignRequest(kind="frontier", profiles=suite("fr", 1),
                                  spec=spec)
    j_cold = svc.submit(req())
    svc.drain()
    j_cached = svc.submit(req())
    svc.drain()
    cold = svc.result(j_cold, timeout=5)
    cached = svc.result(j_cached, timeout=5)
    assert cached is cold
    np.testing.assert_array_equal(cached.objective, cold.objective)
    assert svc.stats["memo_hits"] == 1


def test_bilevel_kind_runs_through_the_funnel():
    """kind="bilevel" rides the same spec funnel: resolves the outer
    budget-split descent, memoizes repeats, renders via the uniform
    result protocol, and rejects a spec with no total_budget."""
    svc = CodesignService(auto_start=False)
    spec = CodesignSpec(total_budget=0.8, outer_steps=2, steps=8, lr=0.1)
    req = lambda: CodesignRequest(kind="bilevel", profiles=suite("bi", 1),
                                  spec=spec)
    j1 = svc.submit(req())
    svc.drain()
    res = svc.result(j1, timeout=5)
    assert res.total_budget == 0.8
    assert res.improvement_over_uniform >= 0.0
    assert abs(res.area_budget + res.power_budget - 0.8) < 1e-12
    json.dumps(res.to_json(top_k=1))
    assert "split" in res.markdown()
    assert "split" in render_result(res, "markdown", top_k=1)
    j2 = svc.submit(req())
    svc.drain()
    assert svc.result(j2, timeout=5) is res  # memo hit
    j3 = svc.submit(CodesignRequest(kind="bilevel",
                                    profiles=suite("bi", 1),
                                    spec=CodesignSpec(steps=2)))
    svc.drain()
    with pytest.raises(ValueError, match="total_budget"):
        svc.result(j3, timeout=5)


def test_frontier_warm_start_from_cached_continuation():
    """A NEW schedule over the same suite/seeds resumes from the nearest
    already-solved budget (cheaper: refine_steps instead of steps)."""
    svc = CodesignService(auto_start=False)
    j1 = svc.submit(CodesignRequest(
        kind="frontier", profiles=suite("warm", 1),
        spec=CodesignSpec(budgets=[0.6, 1.2], steps=4, refine_steps=2)))
    svc.drain()
    assert svc.stats["frontier_warm_hits"] == 0
    tight = CodesignSpec(budgets=[0.5], steps=4, refine_steps=2)
    j2 = svc.submit(CodesignRequest(
        kind="frontier", profiles=suite("warm", 1), spec=tight))
    svc.drain()
    assert svc.stats["frontier_warm_hits"] == 1
    assert svc.poll(j2)["cache"] == "warm"
    warm = svc.result(j2, timeout=5)
    assert warm.budgets.tolist() == [0.5]
    assert bool(warm.feasible.all())
    # the warm seed came from solved state: never worse than running the
    # same schedule cold from the seeds (both deterministic)
    cold = frontier_codesign(suite("warm", 1),
                             MachineBatch.from_models(VARIANTS),
                             spec=tight)
    assert float(warm.objective[0]) <= float(cold.objective[0]) + 1e-9

    # opting out (warm=False) runs cold and skips the cache
    j3 = svc.submit(CodesignRequest(kind="frontier",
                                    profiles=suite("warm", 1), spec=tight,
                                    warm=False))
    svc.drain()
    np.testing.assert_array_equal(svc.result(j3, timeout=5).objective,
                                  cold.objective)


def test_artifact_cache_accounting_same_shape_hits():
    """A same-shape query reuses the jax backend's compiled kernels: the
    jit counters the service reports show no retrace."""
    svc = CodesignService(auto_start=False)
    svc.submit(jax_sweep_req("art1", k=2))
    svc.drain()
    retraces, compiles = svc.stats["retrace"], svc.stats["compile"]
    svc.submit(jax_sweep_req("art2", k=2))  # same (A, V, backend, config)
    svc.drain()
    assert svc.stats["memo_misses"] == 2       # both ran the kernels
    assert svc.stats["retrace"] == retraces
    assert svc.stats["compile"] == compiles


# --------------------------------------------------------------------------- #
# Queue semantics: overload / timeout / cancellation / streaming
# --------------------------------------------------------------------------- #


def test_overload_rejects_429_style():
    svc = CodesignService(auto_start=False, max_pending=2)
    svc.submit(sweep_req("o1"))
    svc.submit(sweep_req("o2"))
    with pytest.raises(ServiceOverloadError) as ei:
        svc.submit(sweep_req("o3"))
    assert ei.value.status_code == 429
    assert svc.stats["rejected"] == 1
    svc.drain()                       # queue drains; capacity frees up
    svc.submit(sweep_req("o3"))
    svc.drain()
    assert svc.stats[DONE] == 3


def test_expired_job_times_out_at_dispatch():
    svc = CodesignService(auto_start=False)
    jid = svc.submit(CodesignRequest(kind="sweep", profiles=suite("t"),
                                     spec=SPEC32, timeout=1e-9))
    time.sleep(0.01)
    svc.drain()
    assert svc.poll(jid)["state"] == TIMEOUT
    with pytest.raises(JobTimeout):
        svc.result(jid, timeout=1)


def test_cancel_pending_job():
    svc = CodesignService(auto_start=False)
    jid = svc.submit(sweep_req("c"))
    assert svc.cancel(jid)
    assert svc.poll(jid)["state"] == CANCELLED
    svc.drain()                            # removed from queue: nothing runs
    assert svc.stats[DONE] == 0
    with pytest.raises(JobCancelled):
        svc.result(jid, timeout=1)
    assert not svc.cancel(jid)             # already terminal


def test_cancel_running_mega_sweep_aborts_between_shards():
    svc = CodesignService(auto_start=False)
    jid = svc.submit(CodesignRequest(kind="mega_sweep", profiles=suite("mc"),
                                     spec=CodesignSpec(n=64), num_shards=4))
    # simulate the cancel landing while the job runs: the progress callback
    # observes the flag at the next shard boundary and unwinds gracefully
    svc._jobs[jid].cancel_requested = True
    svc.drain()
    assert svc.poll(jid)["state"] == CANCELLED
    events = list(svc.stream(jid))
    assert events[-1]["event"] == CANCELLED
    assert sum(e["event"] == "shard" for e in events) <= 1


def test_mega_sweep_streams_shard_progress():
    svc = CodesignService(auto_start=False)
    jid = svc.submit(CodesignRequest(kind="mega_sweep", profiles=suite("ms"),
                                     spec=CodesignSpec(n=64, seed=1),
                                     num_shards=4))
    svc.drain()
    events = list(svc.stream(jid))
    shards = [e for e in events if e["event"] == "shard"]
    assert [s["shard"] for s in shards] == [0, 1, 2, 3]
    assert shards[-1]["hi"] == 64
    assert events[-1]["event"] == DONE
    # stream after completion replays and still terminates
    assert list(svc.stream(jid))[-1]["event"] == DONE


def test_threaded_service_end_to_end():
    """Real worker threads: submit from the test thread, block on results.
    Also covers submit-notify wakeup and concurrent result() waiters."""
    svc = CodesignService(workers=2, max_pending=16, auto_start=True)
    try:
        jids = [svc.submit(sweep_req(f"th{i}")) for i in range(4)]
        results = {}

        def wait(jid):
            results[jid] = svc.result(jid, timeout=60)

        waiters = [threading.Thread(target=wait, args=(j,)) for j in jids]
        for t in waiters:
            t.start()
        for t in waiters:
            t.join(timeout=60)
        assert len(results) == 4
        for i, jid in enumerate(jids):
            assert_sweep_equal(results[jid],
                               run_sweep(suite(f"th{i}"), n=32, seed=0))
    finally:
        svc.shutdown()


# --------------------------------------------------------------------------- #
# Uniform result protocol + renderers
# --------------------------------------------------------------------------- #


def test_every_result_type_implements_the_protocol():
    from repro.core import evaluate
    from repro.core.constrained import constrained_codesign

    profiles = random_profiles(2, seed=3)
    results = [
        run_sweep(profiles, n=8, seed=0),
        evaluate(profiles),
        constrained_codesign(profiles, MachineBatch.from_models(VARIANTS),
                             area_budget=1.0, steps=2),
        frontier_codesign(profiles, MachineBatch.from_models(VARIANTS),
                          budgets=[1.0], steps=2, refine_steps=1),
    ]
    for res in results:
        md_all = render_result(res, "markdown")
        md_top = render_result(res, "markdown", top_k=1)
        assert isinstance(md_all, str) and md_all.count("|") > 3
        assert len(md_top) <= len(md_all)
        blob = render_result(res, "json", top_k=1)
        json.dumps(blob)               # plain data, no numpy leakage


def test_render_rejects_non_protocol_results():
    with pytest.raises(TypeError, match="result protocol"):
        render_result(object(), "markdown")
    with pytest.raises(ValueError, match="unknown render format"):
        render_result(run_sweep(random_profiles(1, seed=0), n=4), "yaml")


def test_sharded_result_renders_through_service():
    svc = CodesignService(auto_start=False)
    jid = svc.submit(CodesignRequest(kind="mega_sweep", profiles=suite("r"),
                                     spec=CodesignSpec(n=64), num_shards=2))
    svc.drain()
    md = svc.render(jid, fmt="markdown", top_k=3, timeout=5)
    assert isinstance(md, str) and "|" in md
    json.dumps(svc.render(jid, fmt="json", top_k=3, timeout=5))


def test_request_validates_at_construction():
    with pytest.raises(ValueError, match="unknown request kind"):
        CodesignRequest(kind="bogus", profiles=suite("v"))
    with pytest.raises(ValueError, match="unknown backend"):
        CodesignRequest(kind="sweep", profiles=suite("v"),
                        spec=CodesignSpec(backend="tpu9000"))


def test_constrained_and_joint_through_the_service():
    svc = CodesignService(auto_start=False)
    jc = svc.submit(CodesignRequest(
        kind="constrained", profiles=suite("cc", 1),
        spec=CodesignSpec(area_budget=1.0, steps=3)))
    jj = svc.submit(CodesignRequest(
        kind="joint", profiles=[suite("jj", 2)],
        spec=CodesignSpec(mode="alternate", steps=4)))
    svc.drain()
    cc = svc.result(jc, timeout=5)
    assert bool(cc.feasible.all())
    jr = svc.result(jj, timeout=5)
    assert jr.mode == "joint-alternate"
    assert "| variant |" in svc.render(jc, fmt="markdown")


# --------------------------------------------------------------------------- #
# BatchedEngine regressions (empty prompt + per-slot KV positions)
# --------------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def engine_setup():
    import jax

    from repro import configs as C
    from repro.models import transformer as T

    cfg = C.get_config("chatglm3-6b", smoke=True)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    return params, cfg


def _solo_generate(params, cfg, prompt, new_tokens):
    from repro.serving.engine import BatchedEngine, Request

    eng = BatchedEngine(params, cfg, slots=1, max_len=32)
    req = Request(rid=0, prompt=list(prompt), max_new_tokens=new_tokens)
    eng.submit(req)
    eng.run_to_completion()
    return req.generated


def test_engine_empty_prompt_admission(engine_setup):
    """Regression: _admit crashed with UnboundLocalError on an empty
    prompt; now it pads with token 0 and still generates."""
    from repro.serving.engine import BatchedEngine, Request

    params, cfg = engine_setup
    eng = BatchedEngine(params, cfg, slots=2, max_len=32)
    req = Request(rid=0, prompt=[], max_new_tokens=3)
    eng.submit(req)
    eng.run_to_completion()
    assert len(req.generated) == 3


def test_engine_staggered_admissions_match_solo(engine_setup):
    """Regression: step() decoded every slot at the SHARED max position,
    corrupting KV for staggered admissions.  Each slot now carries its own
    position vector, so mid-flight admission of new requests leaves
    in-flight generations bit-identical to solo runs."""
    from repro.serving.engine import BatchedEngine, Request

    params, cfg = engine_setup
    prompts = [[1, 2, 3], [4, 5], [7, 8, 9, 10]]
    new_tokens = [5, 5, 3]
    solo = [_solo_generate(params, cfg, p, n)
            for p, n in zip(prompts, new_tokens)]

    eng = BatchedEngine(params, cfg, slots=3, max_len=32)
    reqs = [Request(rid=i, prompt=list(p), max_new_tokens=n)
            for i, (p, n) in enumerate(zip(prompts, new_tokens))]
    eng.submit(reqs[0])
    eng.step()                       # r0 in flight before r1/r2 admit
    eng.submit(reqs[1])
    eng.submit(reqs[2])
    eng.run_to_completion()
    for req, expect in zip(reqs, solo):
        assert req.generated == expect


def test_engine_slot_reuse_after_completion(engine_setup):
    """A freed slot's stale KV never leaks into the next request."""
    from repro.serving.engine import BatchedEngine, Request

    params, cfg = engine_setup
    solo = _solo_generate(params, cfg, [11, 12], 4)
    eng = BatchedEngine(params, cfg, slots=1, max_len=32)
    first = Request(rid=0, prompt=[3, 1, 4], max_new_tokens=3)
    eng.submit(first)
    eng.run_to_completion()
    second = Request(rid=1, prompt=[11, 12], max_new_tokens=4)
    eng.submit(second)
    eng.run_to_completion()
    assert second.generated == solo


# --------------------------------------------------------------------------- #
# Bounded population cache (LRU) + streamed / resumable mega-sweeps
# --------------------------------------------------------------------------- #


def _pop_size_bytes():
    """Bytes one n=32 cached population costs (measured, not assumed)."""
    probe = CodesignService(auto_start=False)
    probe.submit(sweep_req("probe"))
    probe.drain()
    return probe._pop_bytes


def test_population_cache_evicts_lru_under_byte_budget():
    """The cache is bounded: with room for exactly two populations, a
    third insert evicts the least-recently-used one, the byte ledger
    never exceeds the budget, and results are unaffected."""
    size = _pop_size_bytes()
    assert size > 0
    svc = CodesignService(auto_start=False, pop_cache_bytes=2 * size)
    for seed in (0, 1, 2):   # three same-shape, distinct-seed populations
        svc.submit(CodesignRequest(kind="sweep", profiles=suite(f"s{seed}"),
                                   spec=CodesignSpec(n=32, seed=seed)))
        svc.drain()
    assert svc.stats["pop_evictions"] == 1
    assert len(svc._populations) == 2
    assert svc._pop_bytes <= 2 * size
    # seed=0 was evicted -> regenerating is a miss; seed=2 is still hot
    svc.submit(CodesignRequest(kind="sweep", profiles=suite("again0"),
                               spec=CodesignSpec(n=32, seed=0)))
    svc.drain()
    assert svc.stats["pop_misses"] == 4 and svc.stats["pop_hits"] == 0
    svc.submit(CodesignRequest(kind="sweep", profiles=suite("again2"),
                               spec=CodesignSpec(n=32, seed=2)))
    svc.drain()
    assert svc.stats["pop_hits"] == 1


def test_population_cache_serves_oversized_without_caching():
    svc = CodesignService(auto_start=False, pop_cache_bytes=64)
    jid = svc.submit(sweep_req("big"))
    svc.drain()
    assert svc.stats["pop_uncacheable"] == 1
    assert len(svc._populations) == 0 and svc._pop_bytes == 0
    assert_sweep_equal(svc.result(jid, timeout=5),
                       run_sweep(suite("big"), n=32, seed=0))


def test_streamed_mega_sweep_matches_direct_shard_sweep():
    from repro.core import shard_sweep

    svc = CodesignService(auto_start=False)
    jid = svc.submit(CodesignRequest(kind="mega_sweep",
                                     profiles=suite("str"),
                                     spec=CodesignSpec(n=96, seed=2),
                                     num_shards=4, stream=True))
    svc.drain()
    got = svc.result(jid, timeout=5)
    assert got.streamed
    direct = shard_sweep(suite("str"), n=96, seed=2, num_shards=4,
                         stream=True)
    assert got.markdown(top_k=8) == direct.markdown(top_k=8)
    assert got.best_fit_map == direct.best_fit_map
    np.testing.assert_array_equal(got.result.aggregate,
                                  direct.result.aggregate)
    shards = [e for e in svc.stream(jid) if e["event"] == "shard"]
    assert [s["shard"] for s in shards] == [0, 1, 2, 3]
    assert shards[-1]["hi"] == 96


def test_jax_mega_sweep_per_shard_progress_and_cancel():
    """Regression (the distributed-stats path used to emit ONE
    progress(0, 1, 0, V) event): jax-backed mega-sweeps stream one event
    per shard, so cancellation has real boundaries to land on."""
    svc = CodesignService(auto_start=False)
    jid = svc.submit(CodesignRequest(
        kind="mega_sweep", profiles=suite("jx"),
        spec=CodesignSpec(n=64, backend="jax"), num_shards=4))
    svc.drain()
    shards = [e for e in svc.stream(jid) if e["event"] == "shard"]
    assert [s["shard"] for s in shards] == [0, 1, 2, 3]
    assert all(s["num_shards"] == 4 for s in shards)
    # and a cancelled jax job unwinds at a shard boundary, never hangs
    jid2 = svc.submit(CodesignRequest(
        kind="mega_sweep", profiles=suite("jx2"),
        spec=CodesignSpec(n=64, backend="jax"), num_shards=4))
    svc._jobs[jid2].cancel_requested = True
    svc.drain()
    assert svc.poll(jid2)["state"] == CANCELLED
    assert sum(e["event"] == "shard" for e in svc.stream(jid2)) <= 1


def test_cancelled_checkpointed_mega_sweep_resumes(tmp_path):
    """Cancellation + checkpoint_dir compose: the aborted job's last
    completed shard is on disk, and a resume=True resubmission finishes
    from there with a result identical to an uninterrupted run."""
    from repro.core import shard_sweep

    ck = str(tmp_path / "ck")
    kw = dict(kind="mega_sweep", profiles=suite("rs"),
              spec=CodesignSpec(n=96, seed=4), num_shards=4, stream=True)
    svc = CodesignService(auto_start=False)
    jid = svc.submit(CodesignRequest(checkpoint_dir=ck, **kw))
    svc._jobs[jid].cancel_requested = True   # lands at the first boundary
    svc.drain()
    assert svc.poll(jid)["state"] == CANCELLED

    jid2 = svc.submit(CodesignRequest(checkpoint_dir=ck, resume=True, **kw))
    svc.drain()
    resumed = svc.result(jid2, timeout=5)
    assert resumed.resumed_shards == 1       # shard 0 checkpointed pre-abort
    straight = shard_sweep(suite("rs"), n=96, seed=4, num_shards=4,
                           stream=True)
    assert resumed.markdown(top_k=8) == straight.markdown(top_k=8)
    assert resumed.best_fit_map == straight.best_fit_map
    # only the remaining shards streamed on the resumed job
    shards = [e for e in svc.stream(jid2) if e["event"] == "shard"]
    assert [s["shard"] for s in shards] == [1, 2, 3]
