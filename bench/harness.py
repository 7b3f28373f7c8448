"""One run of one cell: weights, engine, warm-up, the measured window, the
reference check. ``bench/run.py`` is the command; the calibration and the
tests call ``run_cell`` directly.

The window drives ``AsyncServingEngine`` in-process: the load generator and
one client per request share its asyncio loop, and ``engine.step`` runs in
the loop's executor thread, wrapped by ``Stepper`` so the benchmark sees
every step's span, batch and context without a change to the program.
"""
from __future__ import annotations

import asyncio
import dataclasses
import gc
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from bench import arch, e2e, reference, traffic, weights
from bench.spec import Spec

now = time.perf_counter
# the reference reads one greedy request from each slot, the longest
# finished greedy request, and more until some hundreds of served tokens
CHECK_TOKENS = 256


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


class CompileClock:
    """Backend compiles (persistent-cache reads included) with the time
    each ended, from jax.monitoring events. Registered once per process."""
    _instance = None

    def __init__(self):
        import jax
        self.events: List[tuple] = []  # (end time, seconds)
        self.hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.events.append((now(), duration))

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    @classmethod
    def get(cls) -> "CompileClock":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t < t1 for t, _ in self.events)


@dataclasses.dataclass
class StepRecord:
    t_start: float
    t_end: float
    contexts: List[int]   # each slot that decoded a token this step: its
    #                       attention context, current token included
    prefill_lens: List[int]  # prompts prefilled whole this step
    pending: bool         # work left when the step ended
    traced: bool

    @property
    def n_decode(self) -> int:
        return len(self.contexts)

    @property
    def context_tokens(self) -> int:
        return sum(self.contexts)


class Stepper:
    """Wraps ``engine.step``: records each step, and at the step boundaries
    after ``trace_from`` / ``trace_until`` snapshots the engine's metrics
    registry and (with ``trace``) starts and stops the profiler, so a
    trace holds whole steps only.

    The profile stays in memory (``profile``, a ``ProfileData``): a
    profiler session's ``stop_and_get_profile_data`` instead of
    ``jax.profiler.stop_trace``, which also writes a JSON trace of every
    event, about 110 s for the 3.5 M device ops of a 51 s ``opt-1.3b``
    window on a TPU v5e, enough to push a traced run past its time.
    jax's public profiler API has no stop that keeps the profile in
    memory, so the session is jax's private ``_profiler.ProfilerSession``,
    as written against jax 0.9.0; ``tests/bench/test_harness.py``'s
    traced rehearsal drives it on the CPU, so a jax that moves it fails
    there."""

    def __init__(self, engine, trace: bool):
        self.engine = engine
        self.inner = engine.step
        engine.step = self.step
        self.trace = trace
        self.session = None
        self.profile = None
        self.records: List[StepRecord] = []
        self.slot_of: Dict[int, int] = {}  # request uid -> slot index
        self.trace_from: Optional[float] = None
        self.trace_until: Optional[float] = None
        self.on = False
        self.done = False
        self.snap0 = self.snap1 = None
        self.span = (None, None)  # host clock of the traced window

    def _boundary(self) -> None:
        t = now()
        if (not self.on and not self.done and self.trace_from is not None
                and t >= self.trace_from):
            self.snap0 = self.engine.obs.registry.snapshot()
            if self.trace:
                import jax
                from jax._src.lib import _profiler
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                self.session = _profiler.ProfilerSession(opts)
            self.on = True
            self.span = (now(), None)
        elif self.on and self.trace_until is not None and t >= self.trace_until:
            self.stop()

    def stop(self) -> None:
        if not self.on:
            return
        self.span = (self.span[0], now())
        self.snap1 = self.engine.obs.registry.snapshot()
        if self.session is not None:
            self.profile = self.session.stop_and_get_profile_data()
            self.session = None
        self.on, self.done = False, True

    def step(self) -> bool:
        self._boundary()
        sched = self.engine.scheduler
        before = {s.request.uid: len(s.out) for s in sched.slots
                  if s is not None}
        t0 = now()
        if self.on and self.trace:
            import jax
            with jax.profiler.StepTraceAnnotation(
                    "engine_step", step_num=len(self.records)):
                worked = self.inner()
        else:
            worked = self.inner()
        t1 = now()
        contexts, pre = [], []
        for i, s in enumerate(sched.slots):
            if s is None:
                continue
            uid = s.request.uid
            self.slot_of.setdefault(uid, i)
            if s.prefilling:
                continue
            if uid not in before:
                pre.append(s.request.prompt_len)
            if len(s.out) > before.get(uid, 1):
                contexts.append(s.next_pos)
        self.records.append(StepRecord(t0, t1, contexts, pre,
                                       sched.has_work(), self.on))
        return worked


# ---------------------------------------------------------------------------
# set-up


def start(chips: int) -> str:
    """Refuse a process without a TPU or with fewer than ``chips`` chips,
    and keep every program in the persistent compile cache, so that only
    a checkout's first run compiles. Returns the cache directory."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise RuntimeError(f"needs a TPU; JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise RuntimeError(f"needs {chips} chips, found {len(devices)}")
    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache


def program_config(mc: dict):
    """The program's configuration for the file's model at the file's
    depth, checked against every field the architecture's
    ``program_fields`` fixes."""
    from repro.configs import get_config
    cfg = get_config(mc["registry_name"]).replace(
        n_layers=arch.dims(mc)["n_layers"], param_dtype="bfloat16",
        compute_dtype=mc["assumed"]["serve_dtype"])
    want = arch.load(mc).program_fields(mc)
    diff = {k: (getattr(cfg, k), v) for k, v in want.items()
            if getattr(cfg, k) != v}
    if diff:
        raise ValueError(f"program config {mc['registry_name']!r} differs "
                         f"from the file: {diff} (program, file)")
    return cfg


def check_layout(cfg, w) -> None:
    """The benchmark's weights must have the program's tree and shapes."""
    import jax
    from repro.models import registry
    fam = registry.get_family(cfg)
    want = jax.eval_shape(lambda: fam.init_params(jax.random.PRNGKey(0),
                                                  cfg))
    a = jax.tree.map(lambda x: (x.shape, str(x.dtype)), want)
    b = jax.tree.map(lambda x: (x.shape, str(x.dtype)), w)
    if a != b:
        raise ValueError(f"weight layout differs from the program's: "
                         f"{a} vs {b}")


def build_engine(spec: Spec, cfg, w, seed: int):
    from repro.serving import ContinuousBatchingEngine, EngineConfig
    c = spec.cell
    if c["max_len"] % c["block_size"]:
        raise ValueError("max_len must be a multiple of block_size")
    return ContinuousBatchingEngine(cfg, w, config=EngineConfig(
        n_slots=c["n_slots"], block_size=c["block_size"],
        max_blocks_per_seq=c["max_len"] // c["block_size"],
        base_seed=seed & 0x7FFFFFFF))


def warm_up(engine, reqs: List[traffic.Request], vocab: int) -> int:
    """Compile every shape the run's requests can produce: the decode step
    and one whole-prompt prefill per prompt block count. Returns the
    number of prefill shapes."""
    bs = engine.block_size
    counts = sorted({-(-len(r.prompt) // bs) for r in reqs})
    rng = np.random.default_rng(0)
    for i, nb in enumerate(counts):
        sp = None
        if i == 0:  # one sampled request: the same executables, exercised
            from repro.serving import SamplingParams
            sp = SamplingParams(temperature=0.8, top_p=0.95, seed=1)
        engine.submit(rng.integers(0, vocab, nb * bs).tolist(), 2,
                      sampling=sp)
    engine.run()
    return len(counts)


# ---------------------------------------------------------------------------
# the measured window


async def _drive(engine, reqs, spec: Spec, seconds: float,
                 stepper: Stepper) -> tuple:
    from repro.serving import AsyncServingEngine, SamplingParams

    mix, cell = spec.mix, spec.cell
    logs = [e2e.ClientLog(r.index, due=None) for r in reqs]
    uids: Dict[int, int] = {}
    tasks: List[asyncio.Task] = []
    state = {"next": 0, "closing": False}
    first_wave = asyncio.Event()

    async with AsyncServingEngine(engine) as api:
        async def client(r: traffic.Request) -> None:
            g = logs[r.index]
            g.sent = now()
            sp = SamplingParams(**r.sampling) if r.sampling else None
            try:
                uid = await api.submit(r.prompt, r.max_new, sampling=sp)
                uids[r.index] = uid
                if state["closing"]:
                    api.cancel(uid)
                async for ev in api.events(uid):
                    t = now()
                    if ev.finished:
                        if ev.finish_reason != "cancelled":
                            g.finished = t
                        break
                    g.token_times.append(t)
                    g.tokens.append(ev.token)
                    if (r.index < cell["n_slots"] and len(g.token_times) == 1
                            and mix["arrival"] == "backlog"):
                        if all(logs[i].token_times
                               for i in range(cell["n_slots"])):
                            first_wave.set()
            except Exception as exc:  # a failed request, not a crash
                g.failed = True
                log(f"request {r.index} failed: {exc!r}")
            if mix["arrival"] == "backlog" and not state["closing"]:
                launch()

        def launch() -> None:
            i = state["next"]
            if i >= len(reqs):
                raise RuntimeError("the backlog pool ran dry: raise 'pool'")
            state["next"] = i + 1
            tasks.append(asyncio.create_task(client(reqs[i])))

        if mix["arrival"] == "backlog":
            for _ in range(cell["n_slots"] + mix["backlog_extra"]):
                launch()
            await first_wave.wait()
            t0 = now()
        else:
            start = now() + 0.05
            t0 = start + mix["warmup_s"]

            async def schedule() -> None:
                for r in reqs:
                    logs[r.index].due = start + r.due
                    delay = logs[r.index].due - now()
                    if delay > 0:
                        await asyncio.sleep(delay)
                    if state["closing"]:
                        return
                    tasks.append(asyncio.create_task(client(r)))

            tasks.append(asyncio.create_task(schedule()))
        t1 = t0 + seconds
        stepper.trace_from, stepper.trace_until = t0, t1
        await asyncio.sleep(max(0.0, t1 - now()))
        state["closing"] = True
        for i, uid in list(uids.items()):
            if logs[i].finished is None:
                api.cancel(uid)
        await asyncio.gather(*tasks)
    for i, uid in uids.items():
        logs[i].slot = stepper.slot_of.get(uid)
    return logs, (t0, t1)


# ---------------------------------------------------------------------------
# correctness


def check_sample(logs, reqs, seed: int, window) -> list:
    """Greedy requests to compare, drawn from the seed: from every slot one
    that received tokens inside the window (finished, or cut at the
    close: the tokens it received are served tokens all the same), the
    longest finished one, then others until ``CHECK_TOKENS`` served
    tokens."""
    live = [g for g in logs if not g.failed and reqs[g.index].sampling is None
            and g.tokens and any(window[0] <= t < window[1]
                                 for t in g.token_times)]
    if not live:
        return []
    order = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 9])
    live = [live[i] for i in order.permutation(len(live))]
    done = [g for g in live if g.finished is not None]
    out = [max(done, key=lambda g: (len(g.tokens), g.index))] if done else []
    slots = {g.slot for g in out}
    for g in live:
        if g.slot is not None and g.slot not in slots:
            slots.add(g.slot)
            out.append(g)
    n = sum(len(g.tokens) for g in out)
    taken = {g.index for g in out}
    for g in live:
        if n >= CHECK_TOKENS:
            break
        if g.index not in taken:
            out.append(g)
            n += len(g.tokens)
    return out


def compare(w, spec: Spec, sample, reqs, control: bool = False) -> dict:
    """Per served token, the gap by which its reference logit lies below
    the reference's best at that position (0 where the run picked the
    reference's argmax), over the sample; with ``control``, the same for
    the token the control puts first. Reports the mean gap, which is
    compared, and the widest gap and the share of tokens off the
    reference's argmax beside it."""
    served, ctrl = [], []
    for g in sample:
        s, c = reference.gaps(w, spec.mc, reqs[g.index].prompt, g.tokens,
                              pad_to=spec.cell["max_len"], control=control)
        served.append(s)
        if control:
            ctrl.append(c)
    out = {"tokens_checked": sum(len(s) for s in served),
           "requests_checked": len(sample),
           "slots_checked": len({g.slot for g in sample} - {None})}
    for key, arrs in (("", served), ("control_", ctrl)):
        if key and not control:
            continue
        a = np.concatenate(arrs) if arrs else np.zeros(0)
        n = len(a)
        out[key + "mean_logit_gap"] = float(a.mean()) if n else None
        out[key + "logit_gap"] = float(a.max()) if n else None
        out[key + "off_argmax"] = float((a > 0).mean()) if n else None
    return out


# ---------------------------------------------------------------------------


def run_cell(spec: Spec, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_kernels: bool = True,
             control: bool = False, fault=None) -> dict:
    """One run. Returns the raw readings; ``bench/report.py`` turns them
    into the result line. ``fault(engine)``, for tests only, breaks the
    timed path after warm-up."""
    import jax

    clock = CompileClock.get()
    mc = spec.mc
    cfg = program_config(mc)
    w = weights.make(mc, seed)
    jax.block_until_ready(w)
    check_layout(cfg, w)
    engine = build_engine(spec, cfg, w, seed)
    if require_kernels and engine.fast_kernels is not True:
        raise RuntimeError("the engine resolved fast_kernels=False: the "
                           "benchmark measures the Pallas decode kernels")
    vocab = arch.dims(mc)["vocab_size"]
    reqs = traffic.generate(spec.mix, spec.cell, seed, seconds, vocab)
    n_shapes = warm_up(engine, reqs, vocab)
    if fault is not None:
        fault(engine)
    stepper = Stepper(engine, trace)
    logs, window = asyncio.run(_drive(engine, reqs, spec, seconds, stepper))
    stepper.stop()
    setup_s = window[0] - t_start
    devices = jax.devices()
    stats = devices[0].memory_stats() or {}
    out = dict(
        spec=spec, seed=seed, logs=logs, window=window, setup_s=setup_s,
        reqs=reqs, records=stepper.records, snaps=(stepper.snap0,
                                                   stepper.snap1),
        span=stepper.span, n_prefill_shapes=n_shapes,
        compiles_in_window=clock.between(*window),
        compiles=len(clock.events), cache_hits=clock.hits,
        fast_kernels=engine.fast_kernels, weight_bytes=weights.nbytes(w),
        device={"platform": devices[0].platform,
                "kind": devices[0].device_kind, "count": len(devices),
                "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))},
        trace=None)
    if stepper.profile is not None:
        from bench import trace as tr
        out["trace"] = tr.reduce(stepper.profile, out["records"])
    # free the program's state before the reference runs: the pool and
    # the engine's buffers go, the benchmark's weights stay
    stepper.engine = None
    del engine, stepper
    gc.collect()
    sample = check_sample(logs, reqs, seed, out["window"])
    out["check"] = compare(w, spec, sample, reqs, control=control)
    return out
