"""Observability tests (repro.obs + its engine/scheduler/API wiring).

The house invariant: observability NEVER changes what the engine computes
— f32 greedy streams are byte-identical with metrics on (the default) and
off (``EngineObs.disabled()``) in all three serving modes, and the hooks'
own cost (``obs.self_time_s``, accumulated inside the hooks with
``perf_counter``) stays a small fraction of the step wall time.

The metrics primitives hold their contracts under hypothesis (the
conftest stub when the real package is absent): snapshot merging is
associative, histogram quantiles always land inside the true quantile's
bucket and the observed [min, max], counters are monotone and reject
negative increments.
"""
from __future__ import annotations

import asyncio
import dataclasses
import gc
import glob
import json
import math
import os
import random
import time
import weakref

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as hst

from repro.configs import get_config
from repro.launch.serve_api import ApiServer, build_engine, parse_args
from repro.obs import (
    PHASES, EngineObs, Histogram, Registry, format_statusz, hist_quantile,
    merge_snapshots, parse_prometheus, render_prometheus, snapshot_quantile,
    tracing,
)
from repro.serving import AsyncServingEngine, ContinuousBatchingEngine

TIMEOUT_S = 300.0

BASE_ARGS = ["--arch", "tiny-relu", "--f32", "--n-slots", "2",
             "--block-size", "8", "--max-blocks", "4", "--gamma", "2"]

MODES = ["plain", "spec", "predictor"]


def _engine(mode: str = "plain", obs_on: bool = True):
    eng = build_engine(parse_args(BASE_ARGS + ["--mode", mode]))
    if not obs_on:
        # swap in the null hub post-build (build_engine always constructs
        # the default enabled one); the scheduler shares the engine's hub
        eng.obs = EngineObs.disabled()
        eng.scheduler.obs = eng.obs
    return eng


def _prompts(n: int = 3, seed: int = 0):
    vocab = get_config("tiny-relu").vocab_size
    rng = np.random.RandomState(seed)
    return [[int(t) for t in rng.randint(0, vocab, 3 + 2 * i)]
            for i in range(n)]


def _serve(eng, prompts, budgets):
    uids = [eng.submit(p, m) for p, m in zip(prompts, budgets)]
    res = eng.run()
    return {u: [int(t) for t in res[u].tokens] for u in uids}


# -- the tentpole invariant: obs on == obs off, byte for byte ----------------


@pytest.mark.parametrize("mode", MODES)
def test_f32_greedy_byte_identical_with_obs_on_and_off(mode):
    prompts, budgets = _prompts(3), [4, 5, 6]
    on = _serve(_engine(mode, obs_on=True), prompts, budgets)
    off = _serve(_engine(mode, obs_on=False), prompts, budgets)
    assert list(on.values()) == list(off.values())


@pytest.mark.parametrize("mode", MODES)
def test_counters_agree_with_served_workload(mode):
    eng = _engine(mode)
    if mode == "spec":
        # the target drafts for itself, so proposals are accepted and the
        # accepted counter is exercised: the launcher's random 1-layer draft
        # agrees with the target about 1/vocab of the time and accepts
        # none of this workload's 15 proposals
        eng = ContinuousBatchingEngine(eng.cfg, eng.params,
                                       config=dataclasses.replace(
                                           eng.config, draft_cfg=eng.cfg,
                                           draft_params=eng.params))
    prompts, budgets = _prompts(3), [4, 5, 6]
    streams = _serve(eng, prompts, budgets)
    obs = eng.obs
    assert obs.c_submitted.value() == len(prompts)
    assert obs.c_admitted.value() == len(prompts)
    assert obs.c_finished.value(reason="length") == len(prompts)
    assert obs.c_tokens.value() == sum(len(s) for s in streams.values())
    assert obs.c_prefill.value() == sum(len(p) for p in prompts)
    assert obs.h_ttft.count() == len(prompts)
    assert obs.h_e2e.count() == len(prompts)
    assert obs.h_queue_wait.count() == len(prompts)
    assert obs.c_steps.value() == obs.steps == eng.t > 0
    # phase histograms cover the phases this mode exercises
    phase_series = set(obs.h_phase.series)
    assert 'phase="prefill"' in phase_series
    assert 'phase="dispatch"' in phase_series
    assert 'phase="sample"' in phase_series
    if mode == "spec":
        assert obs.c_draft_proposed.value() > 0
        assert (0 < obs.c_draft_accepted.value()
                <= obs.c_draft_proposed.value())
    if mode == "predictor":
        assert obs.c_pred_active.value() > 0


def test_obs_self_time_is_a_small_fraction_of_step_time():
    eng = _engine("plain")
    _serve(eng, _prompts(3), [5, 5, 5])
    obs = eng.obs
    step_total = obs.h_step.snapshot()["series"][""]["sum"]
    assert step_total > 0
    # hooks are dict writes + a few floats per step; 10% of step wall (plus
    # a 5 ms absolute floor for coarse timers) is a generous ceiling
    assert obs.self_time_s < 0.10 * step_total + 0.005


def test_disabled_obs_records_nothing():
    eng = _engine("plain", obs_on=False)
    _serve(eng, _prompts(2), [4, 4])
    assert eng.obs.snapshot() == {}
    assert eng.obs.spans == {}
    assert eng.obs.self_time_s == 0.0
    assert eng.obs.render() == ""


# -- metric-helper convention: None for unavailable, 0.0 for zero-so-far ----


@pytest.mark.parametrize("mode", MODES)
def test_metric_helpers_never_raise(mode):
    eng = _engine(mode)
    # fresh engine: nothing measured yet -> None (not a raise, not a fake 0)
    assert eng.predictor_density() is None
    assert eng.predictor_recall() is None
    assert eng.s_agg_window() is None
    assert eng.tile_activity_rate() is None
    assert eng.weight_io_saved() == 0.0
    assert eng.prefix_hit_rate() == 0.0
    snap = eng.metrics_snapshot()
    assert None not in snap.values()
    _serve(eng, _prompts(2), [4, 4])
    snap = eng.metrics_snapshot()
    assert None not in snap.values()
    assert snap["steps"] == eng.t
    if mode == "predictor":
        assert 0.0 < snap["predictor_density"] <= 1.0
        assert 0.0 <= snap["predictor_recall"] <= 1.0
    else:
        assert "predictor_density" not in snap
        assert "predictor_recall" not in snap
    if mode != "spec":
        assert "s_agg_window" not in snap


def test_metrics_omit_unavailable_series():
    eng = _engine("plain")
    _serve(eng, _prompts(2), [4, 4])
    text = eng.obs.render()
    # mode-gated series never fire in plain mode -> absent, not zero
    assert "repro_predictor_active_neurons_total" not in text
    assert "repro_draft_tokens_proposed_total" not in text
    assert "repro_requests_submitted_total 2" in text


# -- /metrics + /statusz over the in-process HTTP wire -----------------------


async def _http_get(port, path):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(f"GET {path} HTTP/1.1\r\nHost: t\r\n\r\n".encode())
    await writer.drain()
    data = await reader.read()
    writer.close()
    try:
        await writer.wait_closed()
    except (ConnectionResetError, BrokenPipeError):
        pass
    head, _, body = data.partition(b"\r\n\r\n")
    return int(head.split(b" ", 2)[1]), body.decode()


def test_http_metrics_statusz_profilez():
    eng = _engine("plain")
    prompt = _prompts(1, seed=9)[0]

    async def serve():
        async with AsyncServingEngine(eng) as api:
            server = ApiServer(api, mode="plain")
            await server.start(port=0)
            try:
                ev = await api.generate(prompt, 4)
                assert ev.finish_reason == "length"
                status, text = await _http_get(server.port, "/metrics")
                assert status == 200
                m = parse_prometheus(text)
                assert m[("repro_requests_submitted_total", "")] == 1
                assert (m[("repro_generated_tokens_total", "")]
                        == len(ev.result.tokens))
                assert m[("repro_api_request_seconds_count", "")] == 1
                status, text = await _http_get(server.port, "/statusz")
                assert status == 200
                assert "repro serving engine" in text
                assert "recently finished" in text
                # profiling is opt-in: no --profilez-dir -> 403, never 500
                status, _ = await _http_get(server.port, "/profilez?ms=5")
                assert status == 403
                status, body = await _http_get(server.port, "/healthz")
                assert status == 200 and json.loads(body)["ok"]
            finally:
                await server.aclose()

    asyncio.run(asyncio.wait_for(serve(), TIMEOUT_S))


def test_json_event_log_covers_the_lifecycle():
    events = []
    eng = _engine("plain")
    eng.obs.log_event = events.append
    _serve(eng, _prompts(1), [4])
    kinds = [e["event"] for e in events]
    for kind in ("submit", "admit", "first_token", "finish"):
        assert kind in kinds, kinds
    finish = events[kinds.index("finish")]
    assert finish["reason"] == "length" and finish["n_tokens"] == 4
    assert all("ts" in e for e in events)
    json.dumps(events)  # the --log-json stream must be JSON-serializable


def test_statusz_renders_for_disabled_obs():
    eng = _engine("plain", obs_on=False)
    _serve(eng, _prompts(1), [3])
    text = format_statusz(eng)
    assert "observability=off" in text
    assert "latency" not in text


# -- profiler spans, host-bound time, GC pauses -----------------------------


def _repro_spans(trace_dir):
    """(name, start_ns, end_ns, host line) of every ``repro.*`` span in the
    capture under ``trace_dir``."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            out += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                     (plane.name, i)) for ev in line.events
                    if ev.name.startswith("repro.")]
    return sorted(out, key=lambda e: e[1])


def test_profiler_capture_nests_phases_in_each_step(tmp_path):
    eng = _engine("plain")
    calls = []
    inner = eng.step

    def step():
        calls.append(1)
        return inner()
    eng.step = step
    prompts = _prompts(3)

    async def serve():
        async with AsyncServingEngine(eng) as api:
            await asyncio.gather(*(api.generate(p, 4) for p in prompts))

    _serve(eng, prompts, [4, 4, 4])  # compile outside the capture
    calls.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        asyncio.run(asyncio.wait_for(serve(), TIMEOUT_S))
    finally:
        jax.profiler.stop_trace()
    spans = _repro_spans(str(tmp_path))
    steps = [e for e in spans if e[0] == "repro.step"]
    assert len(steps) == len(calls) > 0
    order = {f"repro.step.{p}": i for i, p in enumerate(PHASES)}
    seen = set()
    for _, t0, t1, line in steps:
        inside = [e for e in spans if e[0] in order and e[3] == line
                  and t0 <= e[1] and e[2] <= t1]
        ranks = [order[e[0]] for e in inside]
        assert ranks == sorted(ranks)
        seen.update(e[0] for e in inside)
    assert seen == set(order)
    # every phase span lies inside a step span
    assert all(any(s[3] == e[3] and s[1] <= e[1] and e[2] <= s[2]
                   for s in steps) for e in spans if e[0] in order)
    serve_spans = [e for e in spans if e[0].startswith("repro.serve.")]
    assert {e[0] for e in serve_spans} == {"repro.serve.control",
                                          "repro.serve.publish"}
    for _, a, b, _ in serve_spans:  # between steps, never inside one
        assert not any(s[1] < b and a < s[2] for s in steps)


def test_idle_pause_adds_no_host_bound_time():
    eng = _engine("plain")
    obs = eng.obs

    def gap_sum():
        return obs.h_gap.snapshot()["series"][""]["sum"]
    _serve(eng, _prompts(2), [4, 4])  # compiles
    _serve(eng, _prompts(2), [4, 4])
    bound0, gaps0 = obs.c_host_bound.value(), gap_sum()
    assert bound0 > 0 and gaps0 > 0
    time.sleep(0.3)  # an idle server, waiting for requests
    t0 = time.perf_counter()
    _serve(eng, _prompts(2), [4, 4])
    wall = time.perf_counter() - t0
    # what the second burst counted lies inside the burst: the pause
    # before it is in neither the host-bound time nor the step gaps
    assert 0 < obs.c_host_bound.value() - bound0 <= wall
    assert 0 < gap_sum() - gaps0 <= wall


def _gc_count(obs):
    return sum(s["count"] for s in obs.h_gc.snapshot()["series"].values())


def test_gc_pause_is_counted_once_per_live_hub():
    hubs = [EngineObs(), EngineObs(), EngineObs.disabled()]
    eng = _engine("plain")
    ref = weakref.ref(eng.obs)
    hooks = [cb for cb in gc.callbacks if isinstance(cb, tracing._GcPauses)]
    assert len(hooks) == 1
    was_enabled = gc.isenabled()
    gc.disable()
    try:
        before = [_gc_count(h) for h in hubs[:2]]
        gc.collect()
        assert [_gc_count(h) for h in hubs[:2]] == [b + 1 for b in before]
        assert hubs[2].snapshot() == {}
        del eng
        gc.collect()
        assert ref() is None
        assert [cb for cb in gc.callbacks
                if isinstance(cb, tracing._GcPauses)] == hooks
    finally:
        if was_enabled:
            gc.enable()


# -- metrics primitives under hypothesis -------------------------------------


def _hist_from(values, lo=1e-3, factor=2.0, n_buckets=12):
    h = Histogram("h", "", lo=lo, factor=factor, n_buckets=n_buckets)
    for v in values:
        h.observe(v)
    return h


def _values(seed: int, n: int):
    rng = random.Random(seed)
    # span below-lo, in-range, and overflow territory
    return [rng.uniform(1e-4, 50.0) for _ in range(n)]


def _approx_equal(x, y):
    if isinstance(x, dict):
        return (isinstance(y, dict) and x.keys() == y.keys()
                and all(_approx_equal(x[k], y[k]) for k in x))
    if isinstance(x, list):
        return (isinstance(y, list) and len(x) == len(y)
                and all(map(_approx_equal, x, y)))
    if isinstance(x, float):
        return y == pytest.approx(x, rel=1e-9, abs=1e-12)
    return x == y


@settings(max_examples=30)
@given(hst.integers(0, 10 ** 6), hst.integers(1, 40), hst.integers(1, 40),
       hst.integers(1, 40))
def test_merge_snapshots_is_associative(seed, na, nb, nc):
    vals = _values(seed, na + nb + nc)
    snaps = []
    for chunk in (vals[:na], vals[na:na + nb], vals[na + nb:]):
        r = Registry()
        h = r.histogram("h", "x", lo=1e-3, factor=2.0, n_buckets=12)
        c = r.counter("c", "x")
        g = r.gauge("g", "x")
        for v in chunk:
            h.observe(v)
            c.inc(v, kind="a")
        g.set(chunk[-1] if chunk else 0.0)
        snaps.append(r.snapshot())
    a, b, c_ = snaps
    left = merge_snapshots(merge_snapshots(a, b), c_)
    right = merge_snapshots(a, merge_snapshots(b, c_))
    # bucket counts / counts / min / max are exact; the float sums are
    # associative only up to ulp rounding
    assert _approx_equal(left, right)
    merged = merge_snapshots(*snaps)
    assert merged["h"]["series"][""]["count"] == len(vals)
    assert merged["c"]["series"]['kind="a"'] == pytest.approx(sum(vals))
    # and the merged quantile answers from the union
    q = snapshot_quantile(merged, "h", 1.0)
    assert q == pytest.approx(max(vals))


@settings(max_examples=30)
@given(hst.integers(0, 10 ** 6), hst.integers(1, 50),
       hst.floats(0.0, 1.0))
def test_quantile_lands_in_the_true_quantile_bucket(seed, n, q):
    vals = _values(seed, n)
    h = _hist_from(vals)
    got = h.quantile(q)
    assert min(vals) <= got <= max(vals)
    rank = max(1, math.ceil(q * len(vals)))
    true_val = sorted(vals)[rank - 1]
    # got is >= the true quantile and <= its bucket's upper edge
    assert got >= true_val - 1e-12
    upper = next((b for b in h.bounds if true_val <= b), math.inf)
    assert got <= min(upper, max(vals)) + 1e-12


@settings(max_examples=20)
@given(hst.integers(0, 10 ** 6), hst.integers(1, 30))
def test_counter_monotone_and_rejects_negative(seed, n):
    from repro.obs import Counter
    c = Counter("c", "")
    rng = random.Random(seed)
    last = 0.0
    for _ in range(n):
        c.inc(rng.uniform(0, 5))
        assert c.value() >= last
        last = c.value()
    with pytest.raises(ValueError, match="negative"):
        c.inc(-1.0)
    assert c.value() == last  # the failed inc must not corrupt the series


def test_quantile_rejects_out_of_range_q():
    h = _hist_from([1.0])
    with pytest.raises(ValueError, match="outside"):
        h.quantile(1.5)
    assert Histogram("e", "").quantile(0.5) is None  # empty -> None


def test_prometheus_render_parse_roundtrip():
    r = Registry()
    r.counter("repro_x_total", "a counter").inc(3, reason="length")
    r.gauge("repro_g", "a gauge").set(0.5)
    h = r.histogram("repro_h_seconds", "a histogram", lo=1e-3,
                    factor=2.0, n_buckets=4)
    h.observe(0.002)
    h.observe(10.0)  # overflow bucket
    text = r.render()
    assert '# TYPE repro_x_total counter' in text
    assert '# TYPE repro_h_seconds histogram' in text
    m = parse_prometheus(text)
    assert m[("repro_x_total", 'reason="length"')] == 3
    assert m[("repro_g", "")] == 0.5
    assert m[("repro_h_seconds_count", "")] == 2
    assert m[("repro_h_seconds_bucket", 'le="+Inf"')] == 2
    # cumulative buckets: the last finite edge holds only the small obs
    assert m[("repro_h_seconds_bucket", 'le="0.008"')] == 1
    # unobserved metrics are omitted entirely
    assert render_prometheus(Registry().snapshot()) == ""


def test_merge_rejects_mismatched_geometry_and_kind():
    r1, r2 = Registry(), Registry()
    r1.histogram("h", "", lo=1e-3).observe(1.0)
    r2.histogram("h", "", lo=1e-2).observe(1.0)
    with pytest.raises(ValueError, match="bounds"):
        merge_snapshots(r1.snapshot(), r2.snapshot())
    r3 = Registry()
    r3.counter("h", "").inc()
    with pytest.raises(ValueError, match="histogram"):
        merge_snapshots(r1.snapshot(), r3.snapshot())


def test_hist_quantile_single_observation_is_exact():
    h = _hist_from([0.0123])
    for q in (0.0, 0.5, 1.0):
        assert h.quantile(q) == pytest.approx(0.0123)
    s = {"bounds": h.bounds, **h.snapshot()["series"][""]}
    assert hist_quantile(s, 0.5) == pytest.approx(0.0123)
