"""Serving engines for the paper's sparse-inference machinery.

Two tiers:

* ``ContinuousBatchingEngine`` — the production path. Requests are admitted
  and retired mid-decode by a scheduler (serving/scheduler.py); K/V lives in
  a paged block pool shared across the batch (models/common.py) so
  mixed-length sequences coexist without padding to max_len; a SINGLE jitted
  decode step serves every slot, carrying per-request γ-window FFN masks
  (paper Fig. 7c) and per-request tile-activity scores (kernels/fused_ffn)
  through the batch dimension. One trace, no host round-trips in the loop —
  the only per-step host traffic is the (B,) next-token / logprob fetch the
  scheduler needs. Admission can run CHUNKED (``prefill_chunk``): one
  fixed-shape prompt chunk per step interleaved with decode, with shared
  prompt prefixes mapped from a refcounted KV-block cache
  (``prefix_cache``) so identical system prompts are prefilled once.

* ``ServeEngine`` — the legacy single-batch path (fixed max_len contiguous
  cache, per-token python loop), kept as the compatibility surface for
  ``generate()``/``score()`` callers (tests, launch/serve.py) and for the
  instrumented sparsity-measurement runs that want batch-union masks.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.configs.base import ModelConfig
from repro.core.sparsity import AggregatedTracker
from repro.models import common as cm
from repro.models import registry
from repro.models import serving_protocol as sp
from repro.obs import EngineObs
from repro.serving import sampling as smp
from repro.serving.config import EngineConfig
from repro.serving.sampling import SamplingParams
from repro.serving.scheduler import Request, RequestResult, Scheduler
from repro.sharding import rules


@dataclasses.dataclass
class GenerationResult:
    tokens: np.ndarray  # (b, n_new)
    logprobs: Optional[np.ndarray]
    site_sparsity: Dict[str, float]
    aggregated: Optional[AggregatedTracker]
    steps: int


# ---------------------------------------------------------------------------
# continuous batching


def _with_ffn_tiles(nxt, ffn_tiles):
    """The decode step's (b,) sampled tokens, followed by its fused FFN
    kernel's tile counts summed over layers — each slot's named tiles (b,),
    then the tiles fetched — so the one fetch the host makes of the tokens
    brings the counts, and no other transfer. Integers either way."""
    named, fetched = ffn_tiles
    return jnp.concatenate([nxt, jnp.sum(named, 0, dtype=nxt.dtype),
                            jnp.sum(fetched, dtype=nxt.dtype)[None]])


def _place_serve_params(params, mesh):
    """Distribute a param pytree per the serve-mode logical-axis rules
    (weights TP-resident over "model"; sharding/rules.py)."""
    shapes = jax.eval_shape(lambda: params)
    return jax.device_put(params,
                          rules.params_shardings(shapes, mesh, "serve"))


class ContinuousBatchingEngine:
    """Continuous-batching sparse serving over a paged KV cache.

    Constructed as ``ContinuousBatchingEngine(cfg, params,
    config=EngineConfig(...))`` (serving/config.py — a validated dataclass
    holding every field below plus the SLO-scheduling knobs
    ``prefill_budget`` / ``preemption`` / ``aging_steps``). The historical
    keyword form ``ContinuousBatchingEngine(cfg, params, n_slots=4, ...)``
    still works through a deprecation shim that warns once per process and
    round-trips exactly onto an EngineConfig.

    Parameters (= EngineConfig fields)
    ----------------------------------
    n_slots: max concurrently decoding requests (the jitted batch width).
    block_size: tokens per KV block.
    n_blocks: shared pool size (block 0 is scratch). Defaults to full
        residency (every slot can hold max_blocks_per_seq blocks).
    max_blocks_per_seq: static block-table width; bounds prompt+generation
        length to max_blocks_per_seq * block_size tokens.
    track_sparsity: keep a per-request AggregatedTracker (paper Sec. 5.1)
        fed from the in-graph FFN activity (costs one extra host fetch per
        step).
    draft_cfg / draft_params: enable SPECULATIVE mode (paper Sec. 5.2): the
        draft proposes γ tokens per slot (one jitted scan, no host
        round-trips), the target verifies every slot's γ+1-token window in
        ONE jitted forward (causal within the window), and the scheduler
        keeps the longest accepted prefix + the target's correction — so
        greedy output is exactly the autoregressive stream. The verify
        forward's FFN activity comes back unioned per window: its density is
        1 − s_agg(γ), the sparse-verification weight I/O of Thm 1. Requests'
        ``reuse_window`` is ignored in this mode (the verify window IS the
        γ-window; every window refreshes its own union mask).
    gamma: draft length γ per verify window (speculative mode only).
    predictor: enable PREDICTOR mode (the third serving mode): a fitted
        activity predictor (repro.predictor) names each token's active FFN
        tiles BEFORE any FFN weight is read, and the jitted decode step
        gathers ONLY those tiles for both the up- and down-projections
        (kernels/sparse_matmul.py) — fixed-K padded tile indices, so one
        trace serves every step. The predicted mask is composed with the
        γ-window union mask (rows from the current window stay computable),
        and every step measures predicted density + realized recall
        in-graph: a recall miss (masked-out-but-active neuron) is a
        correctness event recorded on RequestResult. Mutually exclusive
        with speculative mode.
    predictor_telemetry: measure realized recall in-graph (predictor mode).
        The probe re-reads the gate weight densely each step — right for
        this measurement repo, wrong for a memory-bound deployment: set
        False in production so the gathered tiles are the ONLY FFN weight
        traffic (recall telemetry then reads 0 and predictor_recall()
        raises instead of reporting a fake 1.0).
    prefill_chunk: > 0 enables CHUNKED PREFILL: admission runs the prompt
        through a fixed (n_slots, prefill_chunk) paged window step
        (transformer.prefill_chunk_paged), ONE chunk per engine step,
        interleaved with decode — bounded per-step admission latency and a
        single compiled prefill shape instead of one per prompt-block
        count. 0 (default) keeps the whole-prompt prefill executable, whose
        bf16 rounding placement is frozen (cross-engine exactness tests pin
        it); at f32 the two paths produce identical greedy streams
        (tests/test_chunked_prefill.py). Composes with all three serving
        modes (the draft pool is chunk-prefilled through the same windows).
    base_seed: PRNG seed behind requests that sample (temperature > 0)
        without their own ``SamplingParams.seed``. Greedy requests never
        consume randomness. See serving/sampling.py for the key-schedule
        contract (restart-deterministic, admission-order independent).
    prefix_cache: reuse KV blocks across requests sharing a token-aligned
        full-block prompt prefix (system prompts, few-shot headers): the
        scheduler's prefix trie maps the shared blocks at admission
        (refcount++), only the cold suffix is prefilled, and retirement
        drops references instead of freeing — cached prefixes persist until
        pool pressure evicts them (LRU, unshared-only). Requires
        prefill_chunk > 0 (the cold suffix resumes mid-prompt, which only
        the chunked path can lower). A cache-hit request's greedy stream is
        byte-identical to a cold prefill of the same prompt.
    warm_masks: with chunked prefill, seed each request's first γ-window
        FFN mask from the prefill chunks' harvested union activity and skip
        the age-0 dense refresh — the request starts decoding with a warm
        mask and one less full weight read (approximation, exactly like any
        other γ-window; off by default so γ phase semantics match the
        whole-prompt path bit for bit).
    mesh: a ("data", "model") jax Mesh makes the engine MESH-NATIVE
        (tensor-parallel sharded serving): params (target, draft, and
        predictor probes) are placed via the serve-mode logical-axis rules
        (sharding/rules.py — FFN wu/wg/wd, attention heads and the vocab
        all split over "model"), the paged KV pool is allocated sharded
        (blocks over "data", kv heads over "model"), the per-slot γ-mask /
        activity buffers split d_ff over "model", and every jitted paged
        step traces under the mesh so its NamedSharding constraints keep
        the sparse FFN machinery shard-local (predictor tile lists pack
        per model shard; telemetry is all-reduced once per step). The
        memory-bound decode reads shrink multiplicatively: sparsity x
        1/TP per device — see ``weight_io_bytes_per_step``. None (the
        default) is today's single-device engine, whose jitted lowerings
        are bit-frozen (bf16 exactness pins); at f32 the sharded engine's
        greedy streams are byte-identical to it in all three serving
        modes (tests/test_sharded_serving.py).
    obs: an ``EngineObs`` observability hub (repro.obs). None (default)
        creates an enabled one per engine: step-phase tracing, per-request
        spans, and labeled counters/histograms feed the ``/metrics`` and
        ``/statusz`` endpoints (launch/serve_api.py); the step's phases
        are also ``jax.profiler.TraceAnnotation`` spans, which a profiler
        capture (``/profilez``) lays beside the device's ops. Hooks only
        touch host-side values the step already fetched — zero added
        device syncs, and f32 greedy streams are byte-identical with
        observability on or off (tests/test_obs.py). Pass
        ``EngineObs.disabled()`` to turn every hook into an early return.
    """

    def __init__(self, cfg: ModelConfig, params,
                 config: Optional[EngineConfig] = None, **legacy_kw):
        if config is None:
            config = (EngineConfig.from_legacy_kwargs(**legacy_kw)
                      if legacy_kw else EngineConfig())
        elif legacy_kw:
            raise TypeError(
                "pass either config=EngineConfig(...) or legacy keyword "
                f"arguments, not both (got {sorted(legacy_kw)})")
        config.validate()
        self.config = config
        n_slots = config.n_slots
        block_size = config.block_size
        max_blocks_per_seq = config.max_blocks_per_seq
        n_blocks = config.resolved_n_blocks
        track_sparsity = config.track_sparsity
        draft_cfg = config.draft_cfg
        draft_params = config.draft_params
        gamma = config.gamma
        predictor = config.predictor
        predictor_telemetry = config.predictor_telemetry
        prefill_chunk = config.prefill_chunk
        prefix_cache = config.prefix_cache
        warm_masks = config.warm_masks
        mesh = config.mesh
        base_seed = config.base_seed
        fast_kernels = config.fast_kernels
        obs = config.obs
        fam = registry.get_family(cfg)
        # every serving-mode gate below goes through the family's DECLARED
        # capability set (models/serving_protocol.py) — one uniform error
        # naming the missing capability, zero hasattr probes
        caps = registry.serving_caps(cfg)
        caps.require("paged_decode", cfg.family)
        if not cfg.d_ff:
            raise ValueError("continuous batching requires an FFN (d_ff > 0)")
        if prefill_chunk:
            caps.require("chunked_prefill", cfg.family)
        self.mesh = mesh
        self.tp = rules.tp_size(mesh)
        # effective TP of the FFN weights: the divisibility guard REPLICATES
        # wu/wg/wd over "model" when d_ff does not divide, and then every
        # device reads the full weight — per-device I/O accounting must not
        # claim a 1/TP split that physically did not happen. MoE shards the
        # EXPERT axis over "model" (sharding/rules.py serve map), so its
        # divisor holds when n_experts divides; d_ff is the fallback axis.
        tp = max(1, self.tp)
        if cfg.n_experts:
            self.ffn_tp = tp if (cfg.n_experts % tp == 0
                                 or cfg.d_ff % tp == 0) else 1
        else:
            self.ffn_tp = tp if cfg.d_ff % tp == 0 else 1
        # fused Pallas decode kernels (kernels/fused_decode.py,
        # kernels/paged_attention.py): None autodetects — compiled kernels
        # on an accelerator, the frozen XLA lowerings on CPU (where the
        # kernels would run in interpret mode: correct but slow, so CPU CI
        # keeps the frozen paths unless a test forces fast_kernels=True).
        if fast_kernels is None:
            fast_kernels = jax.default_backend() != "cpu"
        if fast_kernels and cfg.n_experts:
            import warnings
            warnings.warn(
                "fast_kernels is not wired for MoE serving yet: the fused "
                "decode kernel has no expert-offset variant, so MoE uses "
                "the documented XLA dispatch fallback "
                "(kernels/fused_decode.py); the standalone expert gather "
                "kernels live in kernels/sparse_matmul.py", stacklevel=2)
            fast_kernels = False
        if fast_kernels and mesh is not None:
            import warnings
            warnings.warn(
                "fast_kernels is not available under a mesh: GSPMD cannot "
                "partition pallas_call — falling back to the sharded XLA "
                "serving path", stacklevel=2)
            fast_kernels = False
        self.fast_kernels = bool(fast_kernels)
        fk = self.fast_kernels
        if mesh is not None:
            missing = {"data", "model"} - set(mesh.axis_names)
            if missing:
                raise ValueError("serving mesh needs ('data', 'model') "
                                 f"axes; missing {sorted(missing)}")
            params = _place_serve_params(params, mesh)
        self.cfg = cfg
        self.params = params
        self.fam = fam
        self.block_size = block_size
        self.track = track_sparsity
        self.prefill_chunk = prefill_chunk
        self.warm_masks = warm_masks
        self.prefill_budget = config.prefill_budget
        self.obs = obs if obs is not None else EngineObs()
        self.obs.use_annotations(jax.profiler.TraceAnnotation)
        # preemption resumes a request mid-sequence via chunked prefill of
        # its prompt+generated prefix — without the chunked path the knob
        # is inert (downgraded, not an error: it defaults on)
        self.scheduler = Scheduler(n_slots, n_blocks, block_size,
                                   max_blocks_per_seq,
                                   prefix_cache=prefix_cache, obs=self.obs,
                                   preemption=(config.preemption
                                               and prefill_chunk > 0),
                                   aging_steps=config.aging_steps)
        self.pages = fam.init_paged_cache(
            cfg, n_blocks, block_size,
            sharding=self._pool_sharding(cfg, n_blocks),
            head_dim=self._pool_head_dim(cfg))
        self.masks = jnp.zeros((cfg.n_layers, n_slots, cfg.d_ff), bool,
                               **self._masks_alloc_kw(n_slots))
        self.trackers: Dict[int, AggregatedTracker] = {}
        self.t = 0  # engine step counter
        self._uid = 0
        # weight-I/O accounting, per (active slot, step): autoregressive mode
        # sums the fraction of down-proj rows actually read under γ-reuse
        # (refresh steps count 1.0); speculative mode sums the window's
        # UNION-active fraction = 1 − s_agg (the Sec. 5.2 verification I/O).
        # _tiles_sum tracks active d_ff tiles (kernels/fused_ffn granularity).
        self._dens_sum = 0.0
        self._tiles_sum = 0.0
        self._dens_n = 0
        # predictor-mode recall accounting (in-graph miss counts)
        self._pred_active = 0
        self._pred_miss = 0
        # the current step's mean measured density / tile activity over
        # active slots — stashed by _account() from the SAME numpy arrays
        # it already fetched, so obs.step_end costs no extra device sync
        self._step_density: Optional[float] = None
        self._step_tiles: Optional[float] = None

        vocab = cfg.vocab_size
        self.base_seed = base_seed

        # one jitted sampling head for every closure below: per-slot
        # temperature / top-k / top-p / PRNG keys arrive as TRACED arrays,
        # so mixing greedy and sampled requests in a batch never retraces,
        # and temperature-0 rows reproduce the historical greedy outputs
        # bit for bit (sampling.sample_head's greedy branch is the old
        # argmax + log_softmax formula verbatim)
        def head(logits, temps, tks, tps, keys):
            """(..., vocab_p) -> next token + its logprob per position."""
            return smp.sample_head(logits, vocab, temps, tks, tps, keys)

        def decode(params, pages, table, token, pos, masks, refresh,
                   temps, tks, tps, keys, gen):
            logits, pages, new_masks, (act, scores, density, ffn_tiles) = \
                fam.model_decode_paged(params, pages, table, token, pos, cfg,
                                       masks, refresh, block_size,
                                       fast_kernels=fk)
            nxt, lp = head(logits, temps, tks, tps,
                           smp.position_keys(keys, gen))
            # per-request fraction of active d_ff tiles this step — the
            # granularity the tile-gathered kernels load weights at
            tiles = jnp.mean((scores > 0).astype(jnp.float32), axis=(0, 2))
            return (_with_ffn_tiles(nxt, ffn_tiles), lp, pages, new_masks,
                    tiles, jnp.mean(density, 0), act)

        def prefill(params, tokens, pages, blocks, true_len,
                    temps, tks, tps, keys):
            last, pages = fam.model_prefill_paged(params, {"tokens": tokens},
                                                  cfg, pages, blocks,
                                                  block_size,
                                                  true_len=true_len)
            # the prompt-seeded token is generated index 0 of the schedule
            nxt, lp = head(last, temps, tks, tps,
                           smp.position_keys(keys, jnp.zeros((1,),
                                                             jnp.int32)))
            return nxt[0], lp[0], pages

        # donate the page pool + masks: decode/prefill update them in place
        # instead of copying the whole pool every token
        self._decode = self._jit(decode, donate_argnums=(1, 5))
        # prompts are padded to block multiples, so prefill compiles at most
        # max_blocks_per_seq distinct shapes (admission-path latency bound)
        self._prefill = self._jit(prefill, donate_argnums=(2,))

        if prefill_chunk:
            def prefill_chunk_step(params, pages, table, tokens, pos0, clen,
                                   masks, refresh, keep, temps, tks, tps,
                                   keys, gen):
                (logits, pages, new_masks,
                 (act, _, _, _)) = fam.model_prefill_chunk_paged(
                    params, {"tokens": tokens}, cfg, pages, table, pos0,
                    clen, masks, refresh, block_size, fast_kernels=fk)
                # warm-mask harvest accumulates over a request's chunks:
                # the first chunk REPLACES the slot's row (clearing any
                # stale previous occupant — via new_masks' refresh path),
                # every later chunk ORs its union activity in, so the
                # final mask covers the whole cold suffix
                new_masks = jnp.where(keep[None, :, None], masks | act,
                                      new_masks)
                # every chunk position samples with the slot's CURRENT
                # generated-index key (gen=0 for a fresh prompt; a resumed
                # preempted slot continues its key schedule at len(out)) —
                # only clen-1 (the seed token) is read on the host
                B, C = logits.shape[:2]
                k0 = smp.position_keys(keys, gen)
                nxt, lp = head(logits,
                               jnp.broadcast_to(temps[:, None], (B, C)),
                               jnp.broadcast_to(tks[:, None], (B, C)),
                               jnp.broadcast_to(tps[:, None], (B, C)),
                               jnp.broadcast_to(k0[:, None, :], (B, C, 2)))
                return nxt, lp, pages, new_masks

            self._prefill_chunk = self._jit(prefill_chunk_step,
                                            donate_argnums=(1, 6))

        # -- predictor mode --------------------------------------------------
        self.predictor = predictor
        self.predictor_telemetry = predictor_telemetry
        if predictor is not None:
            caps.require("predictor", cfg.family)
            if predictor.n_tiles * predictor.tile != cfg.d_ff:
                raise ValueError(
                    f"predictor geometry {predictor.n_tiles}x"
                    f"{predictor.tile} does not cover d_ff={cfg.d_ff}")
            if mesh is not None:
                # place the probe weights alongside the FFN weights they
                # shadow (d_ff over "model"); never mutate the caller's
                # Predictor — it may drive other (single-device) engines
                predictor = dataclasses.replace(
                    predictor, params=jax.device_put(
                        predictor.params,
                        rules.predictor_shardings(predictor.params, mesh)))
                self.predictor = predictor
            kind, tile_w = predictor.kind, predictor.tile
            k_tiles = predictor.k_tiles
            # model-axis-local tile packing: each TP shard packs its own
            # capacity from its local d_ff slice (exact at full capacity)
            pred_shards = (self.tp
                           if (cfg.d_ff // tile_w) % self.tp == 0 else 1)
            if self.tp > 1 and pred_shards == 1:
                import warnings
                warnings.warn(
                    f"predictor tile count {cfg.d_ff // tile_w} is not "
                    f"divisible by the {self.tp}-way model axis: packed "
                    "tile lists fall back to GLOBAL packing, so predictor "
                    "gathers will cross shards (correct, but the "
                    "shard-local weight-I/O property is lost)",
                    stacklevel=2)

            def decode_pred(params, pages, table, token, pos, masks, refresh,
                            pred_params, temps, tks, tps, keys, gen):
                logits, pages, new_masks, (act, scores, density, n_act,
                                           n_miss, ffn_tiles) = \
                    fam.model_decode_paged_predicted(
                        params, pages, table, token, pos, cfg, masks,
                        refresh, pred_params, kind, tile_w, k_tiles,
                        block_size, predictor_telemetry, pred_shards,
                        fast_kernels=fk)
                nxt, lp = head(logits, temps, tks, tps,
                               smp.position_keys(keys, gen))
                tiles = jnp.mean((scores > 0).astype(jnp.float32),
                                 axis=(0, 2))
                return (_with_ffn_tiles(nxt, ffn_tiles), lp, pages,
                        new_masks, tiles, jnp.mean(density, 0), act,
                        jnp.sum(n_act, 0), jnp.sum(n_miss, 0))

            self._decode_pred = self._jit(decode_pred, donate_argnums=(1, 5))

        # -- speculative mode ------------------------------------------------
        self.spec = draft_cfg is not None
        self.gamma = gamma
        if self.spec:
            if draft_cfg.vocab_size != cfg.vocab_size:
                raise ValueError("draft and target must share a vocabulary")
            caps.require("spec_verify", cfg.family)
            dfam = registry.get_family(draft_cfg)
            registry.serving_caps(draft_cfg).require("spec_draft",
                                                     draft_cfg.family)
            if mesh is not None:
                draft_params = _place_serve_params(draft_params, mesh)
            self.draft_cfg = draft_cfg
            self.draft_params = draft_params
            self.dfam = dfam
            # the draft shares the slots' block TABLES but has its own pool
            # (its layer count / head geometry differ from the target's)
            self.draft_pages = dfam.init_paged_cache(
                draft_cfg, n_blocks, block_size,
                sharding=self._pool_sharding(draft_cfg, n_blocks),
                head_dim=self._pool_head_dim(draft_cfg))

            def draft(dparams, dpages, table, token, pos0, wlen,
                      temps, tks, tps, keys, gen0):
                # the draft proposes with the SAME per-position key schedule
                # the verify step samples with (key-coupled acceptance —
                # see sampling.py): proposal g uses the key of generated
                # index gen0 + g. Greedy slots fall through to the frozen
                # argmax inside the head.
                def next_fn(logits, g):
                    nxt, _ = smp.sample_head(
                        logits, vocab, temps, tks, tps,
                        smp.position_keys(keys, gen0 + g))
                    return nxt

                return dfam.model_draft_gamma_paged(
                    dparams, dpages, table, token, pos0, wlen, draft_cfg,
                    gamma, block_size, next_fn=next_fn, fast_kernels=fk)

            def verify(params, pages, table, window, pos0, wlen, masks,
                       temps, tks, tps, keys, gen0):
                refresh = jnp.ones((n_slots,), bool)
                logits, pages, new_masks, (act, scores, density, udens) = \
                    fam.model_verify_window_paged(
                        params, pages, table, window, pos0, wlen, cfg,
                        masks, refresh, block_size, fast_kernels=fk)
                B, W = logits.shape[:2]
                nxt, lp = head(logits,  # both (b, W)
                               jnp.broadcast_to(temps[:, None], (B, W)),
                               jnp.broadcast_to(tks[:, None], (B, W)),
                               jnp.broadcast_to(tps[:, None], (B, W)),
                               smp.window_keys(keys, gen0, W))
                tiles = jnp.mean((scores > 0).astype(jnp.float32),
                                 axis=(0, 2))
                return (nxt, lp, pages, new_masks, tiles,
                        jnp.mean(udens, 0), act)

            def prefill_draft(dparams, tokens, dpages, blocks, true_len):
                _, dpages = dfam.model_prefill_paged(
                    dparams, {"tokens": tokens}, draft_cfg, dpages, blocks,
                    block_size, true_len=true_len)
                return dpages

            self._draft = self._jit(draft, donate_argnums=(1,))
            self._verify = self._jit(verify, donate_argnums=(1, 6))
            self._prefill_draft = self._jit(prefill_draft, donate_argnums=(2,))

            if prefill_chunk:
                def prefill_chunk_draft(dparams, dpages, table, tokens,
                                        pos0, clen):
                    # the draft needs the prompt K/V in ITS pool too. Its
                    # own γ-masks never persist (the returned masks are
                    # discarded), but refresh MUST be on: refresh off with
                    # zero masks silently zeroes the FFN (eff = mask |
                    # refresh), corrupting the drafted prompt K/V — exact
                    # output either way, but acceptance would collapse
                    dmasks = jnp.zeros((draft_cfg.n_layers, n_slots,
                                        draft_cfg.d_ff), bool)
                    drefresh = jnp.ones((n_slots,), bool)
                    _, dpages, _, _ = dfam.model_verify_window_paged(
                        dparams, dpages, table, tokens, pos0, clen,
                        draft_cfg, dmasks, drefresh, block_size,
                        fast_kernels=fk)
                    return dpages

                self._prefill_chunk_draft = self._jit(prefill_chunk_draft,
                                                      donate_argnums=(1,))

        self.obs.set_engine_info(
            arch=cfg.name,
            mode=("spec" if self.spec
                  else "predictor" if self.predictor is not None
                  else "plain"),
            n_slots=n_slots, block_size=block_size,
            prefill_chunk=prefill_chunk, tp=self.tp,
            fast_kernels=self.fast_kernels, family=cfg.family,
            n_experts=cfg.n_experts)

    # -- mesh plumbing -------------------------------------------------------
    def _jit(self, fn, **kw):
        """jax.jit whose *calls* run under the engine's mesh: constraints in
        the paged steps (rules.constrain) bind at trace time, so the mesh
        must be installed exactly while a sharded engine traces — and never
        while a single-device engine does (mesh=None skips the wrapper
        entirely: the frozen lowerings stay byte-identical)."""
        jf = jax.jit(fn, **kw)
        if self.mesh is None:
            return jf
        mesh = self.mesh

        def call(*args):
            with rules.use_mesh(mesh):
                return jf(*args)
        return call

    def _pool_sharding(self, cfg_: ModelConfig, n_blocks: int):
        """NamedSharding for a paged KV pool (None single-device): blocks
        over "data", kv heads over "model" — allocated in place, a
        production pool must never materialize on one device first."""
        if self.mesh is None:
            return None
        g = cm.HeadGeometry(cfg_.n_heads, cfg_.n_kv_heads,
                            cfg_.resolved_head_dim)
        shape = (cfg_.n_layers, n_blocks, g.kvp, self.block_size, g.head_dim)
        return NamedSharding(self.mesh,
                             rules.paged_cache_pspec(shape, self.mesh))

    def _pool_head_dim(self, cfg_: ModelConfig) -> Optional[int]:
        """Head width the KV pool stores: whole 128-lane tiles on the fast
        path (kernels/paged_attention.py ``pool_head_dim``), the model's
        own off it (None) — the frozen lowerings keep their pool."""
        if not self.fast_kernels:
            return None
        from repro.kernels import paged_attention as kpa
        return kpa.pool_head_dim(cfg_.resolved_head_dim)

    def _masks_alloc_kw(self, n_slots: int) -> Dict:
        """Allocation kwargs for the (L, n_slots, d_ff) γ-mask buffer:
        d_ff over "model" so mask updates stay shard-local."""
        if self.mesh is None:
            return {}
        shape = (self.cfg.n_layers, n_slots, self.cfg.d_ff)
        return {"device": NamedSharding(
            self.mesh, rules.serve_masks_pspec(shape, self.mesh))}

    # -- request API --------------------------------------------------------
    def submit(self, prompt, max_new: int, reuse_window: int = 0,
               sampling: Optional[SamplingParams] = None, *,
               priority: int = 0,
               slo_ms: Optional[float] = None) -> int:
        """Enqueue a request; returns its uid. Admission happens inside
        step() when a slot and enough KV blocks are free.

        ``sampling`` (None = greedy) selects this request's decoding
        distribution and stop sequences. A sampled request's PRNG key is
        derived here from (seed, request fingerprint) — never from the
        uid, slot, or admission order — so its stream replays identically
        whatever else is co-scheduled (serving/sampling.py).

        ``priority`` (higher = more urgent; default 0) orders admission
        and selects preemption victims; aging (EngineConfig.aging_steps)
        keeps low classes from starving. ``slo_ms`` is this request's
        time-to-first-token target: it never changes scheduling, it is
        judged (RequestResult.slo_met, /metrics) — the scheduler works on
        priorities, the SLO grades the outcome."""
        self._uid += 1
        key = None
        if sampling is not None and not sampling.is_greedy:
            key = smp.request_prng_key(prompt, sampling, self.base_seed)
        req = Request(uid=self._uid,
                      tokens=np.asarray(prompt, np.int32).reshape(-1),
                      max_new=max_new, reuse_window=reuse_window,
                      sampling=sampling, key=key,
                      priority=priority, slo_ms=slo_ms)
        self.scheduler.submit(req, self.t)
        return self._uid

    def cancel(self, uid: int) -> bool:
        """Abandon a request (client disconnect). Queued requests are
        withdrawn immediately; in-flight ones finish this step and retire
        with their partial output and finish_reason "cancelled". Returns
        False for unknown/finished uids."""
        return self.scheduler.cancel(uid)

    def _admit(self, st=None) -> bool:
        """Retire finished requests, admit queued ones, and advance prefill
        (into the draft's page pool too, in speculative mode). ``st`` is
        the step's phase trace; standalone callers (tests driving prefill
        chunk-by-chunk) may omit it and get a throwaway one.

        Whole-prompt mode (prefill_chunk == 0): every newly admitted
        request is prefilled to completion right here — the frozen legacy
        lowering. Chunked mode: ONE fixed-shape (n_slots, prefill_chunk)
        window step advances EVERY prefilling slot by one chunk, so
        admission work is interleaved with (and latency-bounded like) the
        decode step; slots whose prompt completes are seeded from that
        chunk's logits. Returns True when any prefill work ran.

        ``st`` is the step's StepTrace: retirement + admission time under
        "admit", all prefill work (whole-prompt or one chunk, including its
        host fetches) under "prefill"."""
        if st is None:
            st = self.obs.step_start()  # throwaway trace, never reported
        sched = self.scheduler
        with st.phase("admit"):
            sched.retire_finished(self.t)
            newly = sched.admit(self.t)
            if self.track:
                for _, slot in newly:
                    # a resumed (preempted) slot keeps its tracker: the
                    # union statistics span the whole logical request
                    if slot.request.uid not in self.trackers:
                        self.trackers[slot.request.uid] = AggregatedTracker(
                            self.cfg.n_layers, self.cfg.d_ff)
        if not self.prefill_chunk:
            if not newly:
                return False
            with st.phase("prefill"):
                self._prefill_whole(newly)
            return True
        if not sched.prefill_indices():
            return False
        with st.phase("prefill"):
            self._prefill_one_chunk()
        return True

    def _prefill_whole(self, newly) -> None:
        """Whole-prompt prefill of every newly admitted slot (the frozen
        legacy lowering — prefill_chunk == 0)."""
        sched = self.scheduler
        for _, slot in newly:
            s = slot.prefill_len
            nb_eff = -(-s // self.block_size)  # blocks the prompt holds
            toks = np.zeros((1, nb_eff * self.block_size), np.int32)
            toks[0, :s] = slot.prefill_tokens
            jt = jnp.asarray(toks)
            blocks = jnp.asarray(slot.blocks[:nb_eff], jnp.int32)
            true_len = jnp.asarray(s, jnp.int32)
            sp = slot.request.sampling or smp.GREEDY
            rkey = (slot.request.key if slot.request.key is not None
                    else np.zeros((2,), np.uint32))
            nxt, lp, self.pages = self._prefill(
                self.params, jt, self.pages, blocks, true_len,
                jnp.asarray([sp.temperature], jnp.float32),
                jnp.asarray([sp.top_k], jnp.int32),
                jnp.asarray([sp.top_p], jnp.float32),
                jnp.asarray(rkey[None, :]))
            self.obs.launched()
            if self.spec:
                self.draft_pages = self._prefill_draft(
                    self.draft_params, jt, self.draft_pages, blocks,
                    true_len)
            tok, tok_lp = int(nxt), float(lp)
            self.obs.fetched()
            sched.seed(slot, tok, tok_lp, step=self.t)

    def _prefill_one_chunk(self) -> None:
        """One fixed-shape chunked-prefill window step (see _admit)."""
        sched = self.scheduler
        (tokens, pos0, table, clen,
         first) = sched.prefill_batch(self.prefill_chunk,
                                      self.prefill_budget)
        temps, tks, tps, skeys, gen = sched.sampling_arrays()
        # prefilling slots run DENSE (refresh on): the chunk records fresh
        # union activity into their mask rows — the warm-mask harvest, and
        # harmless otherwise (an age-0 decode refresh overwrites it).
        # Decoding slots keep refresh off so their live γ-masks survive
        # the shared (L, B, F) mask update; continuing chunks (keep) OR
        # into the running union instead of replacing it.
        refresh = clen > 0
        keep = refresh & ~first
        jt = jnp.asarray(table)
        jtok, jp, jc = (jnp.asarray(tokens), jnp.asarray(pos0),
                        jnp.asarray(clen))
        nxt, lp, self.pages, self.masks = self._prefill_chunk(
            self.params, self.pages, jt, jtok, jp, jc, self.masks,
            jnp.asarray(refresh), jnp.asarray(keep), jnp.asarray(temps),
            jnp.asarray(tks), jnp.asarray(tps), jnp.asarray(skeys),
            jnp.asarray(gen))
        self.obs.launched()
        if self.spec:
            self.draft_pages = self._prefill_chunk_draft(
                self.draft_params, self.draft_pages, jt, jtok, jp, jc)
        nxt_np, lp_np = np.asarray(nxt), np.asarray(lp)
        self.obs.fetched()
        sched.record_prefill(nxt_np, lp_np, clen, warm=self.warm_masks,
                             step=self.t)

    def _account(self, active, dens_np, tiles_np, act) -> None:
        """Per-(active slot, step) weight-I/O + sparsity-tracker updates.
        Also stashes the step means for obs.step_end — derived from the
        numpy arrays this call already received, not a new fetch."""
        self.scheduler.record_io(active, dens_np)
        for i in active:
            self._dens_sum += float(dens_np[i])
            self._tiles_sum += float(tiles_np[i])
            self._dens_n += 1
        if active:
            self._step_density = float(np.mean(dens_np[active]))
            self._step_tiles = float(np.mean(tiles_np[active]))
        if self.track:
            act_np = np.asarray(act)  # (L, B, F)
            for i in active:
                uid = self.scheduler.slots[i].request.uid
                self.trackers[uid].update(act_np[:, i, :])

    def _count_ffn_tiles(self, out_np, active) -> np.ndarray:
        """Split a decode step's fetched tokens from the fused FFN kernel's
        tile counts that ride with them (``_with_ffn_tiles``), count the
        tiles fetched and the (active slot, named tile) pairs, and return
        the (B,) tokens."""
        B = self.scheduler.n_slots
        if self.fast_kernels:
            self.obs.ffn_tiles(int(out_np[2 * B]),
                               int(out_np[B:2 * B][active].sum()))
        return out_np[:B]

    def step(self) -> bool:
        """Retire finished requests, admit queued ones, advance prefill by
        one chunk (chunked mode), then advance every active slot: one
        decoded token each (autoregressive mode) or one drafted-and-verified
        γ-window each (speculative mode). Returns False when NO work ran —
        neither a prefill chunk nor a decode."""
        st = self.obs.step_start()
        self._step_density = self._step_tiles = None
        prefilled = self._admit(st)
        active = self.scheduler.active_indices()
        if active:
            if self.spec:
                self._advance_spec(active, st)
            elif self.predictor is not None:
                self._advance_pred(active, st)
            else:
                self._advance(active, st)
        elif not prefilled:
            self._obs_step_end(st, False, active)
            return False
        self.t += 1
        self._obs_step_end(st, True, active)
        return True

    def _obs_step_end(self, st, worked: bool, active) -> None:
        """Close the step's trace with host-side state only (occupancy,
        pool, queue, the density _account() already stashed, and the
        decoded slots' positions after the step)."""
        if not self.obs.enabled:
            return
        sched = self.scheduler
        self.obs.step_end(
            st, worked=worked, slots_active=len(active),
            n_slots=sched.n_slots, queue_depth=len(sched.queue),
            pool_used=sched.allocator.allocated,
            pool_total=sched.allocator.n_blocks - 1,
            pending=bool(sched.queue) or any(
                s is not None and not s.done for s in sched.slots),
            decode_slots=len(active),
            context_tokens=sum(sched.slots[i].next_pos for i in active),
            density=self._step_density, tiles=self._step_tiles)

    def _advance(self, active, st) -> None:
        """Decode one token for every active slot."""
        sched = self.scheduler
        with st.phase("dispatch"):
            tokens, pos, table, refresh = sched.batch_arrays()
            temps, tks, tps, keys, gen = sched.sampling_arrays()
            nxt, lp, self.pages, self.masks, tiles, dens, act = self._decode(
                self.params, self.pages, jnp.asarray(table),
                jnp.asarray(tokens), jnp.asarray(pos), self.masks,
                jnp.asarray(refresh), jnp.asarray(temps), jnp.asarray(tks),
                jnp.asarray(tps), jnp.asarray(keys), jnp.asarray(gen))
            self.obs.launched()
        with st.phase("host_sync"):
            dens_np, tiles_np = np.asarray(dens), np.asarray(tiles)
            nxt_np, lp_np = np.asarray(nxt), np.asarray(lp)
            self.obs.fetched()
        with st.phase("sample"):
            nxt_np = self._count_ffn_tiles(nxt_np, active)
            self._account(active, dens_np, tiles_np, act)
            sched.record(nxt_np, lp_np)

    def _advance_pred(self, active, st) -> None:
        """Predictor-mode decode: per-token predicted tile masks drive
        gathered up+down FFN matmuls inside the single jitted decode step;
        density / recall telemetry comes back with the batch."""
        sched = self.scheduler
        with st.phase("dispatch"):
            tokens, pos, table, refresh = sched.batch_arrays()
            temps, tks, tps, keys, gen = sched.sampling_arrays()
            (nxt, lp, self.pages, self.masks, tiles, dens, act, n_act,
             n_miss) = self._decode_pred(
                self.params, self.pages, jnp.asarray(table),
                jnp.asarray(tokens), jnp.asarray(pos), self.masks,
                jnp.asarray(refresh), self.predictor.params,
                jnp.asarray(temps), jnp.asarray(tks), jnp.asarray(tps),
                jnp.asarray(keys), jnp.asarray(gen))
            self.obs.launched()
        with st.phase("host_sync"):
            dens_np, tiles_np = np.asarray(dens), np.asarray(tiles)
            na, nm = np.asarray(n_act), np.asarray(n_miss)
            nxt_np, lp_np = np.asarray(nxt), np.asarray(lp)
            self.obs.fetched()
        with st.phase("sample"):
            nxt_np = self._count_ffn_tiles(nxt_np, active)
            self._account(active, dens_np, tiles_np, act)
            step_act = step_miss = 0
            for i in active:
                step_act += int(na[i])
                step_miss += int(nm[i])
            self._pred_active += step_act
            self._pred_miss += step_miss
            if self.predictor_telemetry:
                self.obs.predictor_counts(step_act, step_miss)
            sched.record(nxt_np, lp_np, pred_density=dens_np,
                         pred_active=na, pred_miss=nm)

    def _advance_spec(self, active, st) -> None:
        """Speculative decode, batched across slots: γ draft tokens per
        slot from ONE jitted draft scan, then every slot's whole γ+1
        window through ONE jitted target forward. The only host traffic is
        the (B, γ) proposal fetch and the (B, W) target-token/logprob fetch
        the acceptance bookkeeping needs — no per-token round-trips. Both
        the draft scan and the verify head consume the slots' shared
        per-position key schedule, so sampled requests come out identical
        to their autoregressive sampled streams (key-coupled acceptance —
        serving/sampling.py)."""
        sched = self.scheduler
        with st.phase("dispatch"):
            tokens, pos0, table, wlen = sched.spec_batch(self.gamma + 1)
            temps, tks, tps, keys, gen0 = sched.sampling_arrays()
            jt = jnp.asarray(table)
            jp, jw = jnp.asarray(pos0), jnp.asarray(wlen)
            jtemps, jtks, jtps = (jnp.asarray(temps), jnp.asarray(tks),
                                  jnp.asarray(tps))
            jkeys, jgen = jnp.asarray(keys), jnp.asarray(gen0)
            props, self.draft_pages = self._draft(
                self.draft_params, self.draft_pages, jt, jnp.asarray(tokens),
                jp, jw, jtemps, jtks, jtps, jkeys, jgen)
            self.obs.launched()
            # the (B, γ) proposal fetch is pipeline-necessary (the verify
            # window is built from it), so it stays in "dispatch"
            props_np = np.asarray(props)
            self.obs.fetched()
            window = np.concatenate([tokens[:, None], props_np], axis=1)
            (target, lp, self.pages, self.masks, tiles, udens,
             act) = self._verify(
                self.params, self.pages, jt, jnp.asarray(window), jp, jw,
                self.masks, jtemps, jtks, jtps, jkeys, jgen)
            self.obs.launched()
        with st.phase("host_sync"):
            udens_np, tiles_np = np.asarray(udens), np.asarray(tiles)
            target_np, lp_np = np.asarray(target), np.asarray(lp)
            self.obs.fetched()
        with st.phase("sample"):
            self._account(active, udens_np, tiles_np, act)
            sched.record_spec(window, target_np, lp_np, wlen)

    def drain(self, max_steps: int = 1_000_000) -> Dict[int, RequestResult]:
        """Drive step() until every submitted request has finished.

        Never drops work silently: if step() makes no progress while
        requests remain queued (a head that can never be admitted — which
        submit()'s validation should have rejected), or max_steps runs out
        with work outstanding, this RAISES instead of returning a results
        dict with uids quietly missing."""
        for _ in range(max_steps):
            progressed = self.step()
            if not self.scheduler.has_work():
                break
            if not progressed:
                # step() already retired + attempted admission: with no
                # active slot, no prefill chunk, and the queue head still
                # stuck, no internal event can ever unblock it
                alloc = self.scheduler.allocator
                raise RuntimeError(
                    f"serving deadlock: queued requests "
                    f"{self.scheduler.queue.uids()} can never be admitted "
                    f"({alloc.available}/{alloc.n_blocks - 1} pool blocks "
                    f"free, every slot idle)")
        else:
            if self.scheduler.has_work():
                raise RuntimeError(
                    f"drain(max_steps={max_steps}) exhausted with "
                    f"{len(self.scheduler.queue)} request(s) still queued "
                    f"or in flight")
        self.scheduler.retire_finished(self.t)
        return dict(self.scheduler.results)

    def run(self, max_steps: int = 1_000_000) -> Dict[int, RequestResult]:
        """Offline convenience: submit everything first, then run to
        completion. A thin wrapper over ``drain`` — the online serving
        layer (serving/api.py) interleaves submit()/cancel() with step()
        instead and never calls this."""
        return self.drain(max_steps)

    # -- metrics ------------------------------------------------------------
    # Scalar-helper convention (and the one metrics_snapshot()/the /metrics
    # endpoint rely on to OMIT series instead of faking them):
    #   * cumulative work ratios that are well-defined as "nothing saved
    #     yet" return 0.0 on a fresh engine (weight_io_saved,
    #     prefix_hit_rate);
    #   * mode-gated or measurement-gated metrics return None when the
    #     serving mode / telemetry doesn't produce them OR no step has
    #     measured them yet (predictor_density, predictor_recall,
    #     s_agg_window, tile_activity_rate) — never a fake 1.0 and never
    #     a raise, so status surfaces can render any engine uniformly.

    def weight_io_saved(self) -> float:
        """Fraction of FFN weight reads skipped, averaged over (active
        slot, step). Autoregressive mode: down-projection rows skipped by
        γ-window reuse (0.0 for dense serving). Speculative mode: skipped
        by verifying with only the window's union-active rows — the
        measured s_agg(γ) of paper Sec. 5.2 / Thm 1. Predictor mode:
        up- AND down-projection tiles skipped because the predictor never
        gathered them (1 − mean predicted tile density)."""
        if not self._dens_n:
            return 0.0
        return 1.0 - self._dens_sum / self._dens_n

    def _mode_ffn_bytes(self) -> int:
        """Per-layer-pass FFN weight bytes in the serving mode's SKIPPABLE
        scope, per token, dense: the down-projection for γ-reuse /
        speculative serving (their density metric covers wd rows), up-,
        gate- AND down-projection for predictor serving (the predictor
        gathers all of them). With ``fast_kernels`` the autoregressive
        step ALSO runs its up/gate projections through the fused
        tile-gathered kernel (kernels/fused_decode.py) over the γ-mask's
        tile list, widening the skippable scope to every projection — the
        speculative window's up projection stays dense (the union is only
        known after it runs), so its scope is unchanged.

        MoE: the dense scope covers ALL experts (× n_experts) — routing is
        itself structured activation sparsity, so the top-k gather is part
        of the measured density (the family reports density =
        activated/total experts × within-expert density), and
        ``weight_io_bytes_per_step`` = density × this dense-all-experts
        figure is the activated-expert bytes actually read."""
        itemsize = jnp.dtype(self.cfg.compute_dtype).itemsize
        proj = self.cfg.d_ff * self.cfg.d_model * itemsize
        proj *= max(1, self.cfg.n_experts)
        n_all = 3 if self.cfg.ffn_kind == "glu" else 2
        if self.predictor is not None:
            return self.cfg.n_layers * n_all * proj
        if self.fast_kernels and not self.spec:
            return self.cfg.n_layers * n_all * proj
        return self.cfg.n_layers * proj

    def weight_io_bytes_per_step(self, per_device: bool = True) -> float:
        """Mean FFN weight bytes actually READ per (active slot, step) over
        the mode's skippable projections (``_mode_ffn_bytes``). With a mesh
        the default is the PER-DEVICE figure: TP shards the d_ff axis of
        exactly the tiles the sparsity machinery masks, so each device
        reads measured_density x dense_bytes / TP — the multiplicative
        sparsity x 1/TP shrink of the memory-bound decode step. The
        divisor is ``ffn_tp``, NOT the raw mesh TP: when d_ff does not
        divide the model axis the guard replicated the FFN weights and
        every device really reads them whole. per_device=False reports the
        mesh-wide total (== the single-device engine's figure at equal
        density)."""
        dens = 1.0 if not self._dens_n else self._dens_sum / self._dens_n
        total = dens * self._mode_ffn_bytes()
        return total / self.ffn_tp if per_device else total

    def predictor_density(self) -> Optional[float]:
        """Mean fraction of FFN weight tiles gathered per (active slot,
        step, layer) in predictor mode — the up+down weight-I/O actually
        paid. None outside predictor mode or before any measured step."""
        if self.predictor is None or not self._dens_n:
            return None
        return self._dens_sum / self._dens_n

    def predictor_recall(self) -> Optional[float]:
        """Realized recall, measured in-graph across every served token:
        1 − (active neurons the predictor's gathered tiles missed) /
        (active neurons). A miss is a correctness event — at recall 1.0 the
        predictor-mode stream is the dense greedy stream. None when recall
        was never measured: outside predictor mode, with
        ``predictor_telemetry=False`` (the in-graph probe reads 0 — a fake
        1.0 would hide that nothing was checked), or before any decode
        step."""
        if (self.predictor is None or not self.predictor_telemetry
                or not self._dens_n):
            return None
        if not self._pred_active:
            return 1.0  # measured: no neuron fired, so none was missed
        return 1.0 - self._pred_miss / self._pred_active

    def s_agg_window(self) -> Optional[float]:
        """Measured mean aggregated sparsity per verify window (speculative
        mode): 1 − mean fraction of FFN units active anywhere in a γ-window.
        None outside speculative mode or before any verify window ran."""
        if not self.spec or not self._dens_n:
            return None
        return self.weight_io_saved()

    def tile_activity_rate(self) -> Optional[float]:
        """Mean fraction of d_ff tiles with any live activation, per (active
        slot, step) — what a tile-gathered down-projection would load.
        None before any measured step."""
        if not self._dens_n:
            return None
        return self._tiles_sum / self._dens_n

    def expert_io_fraction(self) -> Optional[float]:
        """Fraction of expert FFN weights a token's routing activates:
        top_k / n_experts — the coarse-grained layer of the activated-
        expert byte accounting (``weight_io_bytes_per_step`` multiplies it
        by the measured within-expert density via the family's density
        telemetry). Exact under drop-free capacity (every token reads
        exactly its top-k experts' tiles; dropped slots only read less, so
        this is the upper bound actually provisioned for). None for
        non-MoE families."""
        if not self.cfg.n_experts:
            return None
        return self.cfg.top_k / self.cfg.n_experts

    def prefix_hit_rate(self) -> float:
        """Fraction of admitted prompt tokens served from the prefix cache
        (their prefill — compute AND KV writes — was skipped entirely).
        0.0 when the cache is off or nothing was admitted yet."""
        s = self.scheduler
        if not s.prefill_tokens_total:
            return 0.0
        return s.prefill_tokens_saved / s.prefill_tokens_total

    def prefill_tokens_saved(self) -> int:
        """Total prompt tokens whose prefill was skipped via cached prefix
        blocks, across every admitted request."""
        return self.scheduler.prefill_tokens_saved

    def metrics_snapshot(self) -> Dict[str, float]:
        """Every scalar engine metric that is currently AVAILABLE (the
        None-valued ones — wrong mode, telemetry off, nothing measured yet
        — are omitted, per the convention above). The /statusz endpoint,
        launch/serve.py's final report, and tests consume this instead of
        probing helpers one by one."""
        out = {
            "steps": float(self.t),
            "weight_io_saved": self.weight_io_saved(),
            "weight_io_bytes_per_step": self.weight_io_bytes_per_step(),
            "tile_activity_rate": self.tile_activity_rate(),
            "prefix_hit_rate": self.prefix_hit_rate(),
            "prefill_tokens_saved": float(self.prefill_tokens_saved()),
            "predictor_density": self.predictor_density(),
            "predictor_recall": self.predictor_recall(),
            "s_agg_window": self.s_agg_window(),
            "expert_io_fraction": self.expert_io_fraction(),
        }
        return {k: v for k, v in out.items() if v is not None}


# ---------------------------------------------------------------------------
# legacy single-batch path (compatibility: generate()/score() callers)


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, max_len: int = 256,
                 track_sparsity: bool = False):
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.fam = registry.get_family(cfg)
        self.track = track_sparsity
        self._decode_jit = jax.jit(
            lambda p, c, t, pos: self.fam.model_decode(p, c, t, pos, cfg))

    # -- basic API ----------------------------------------------------------
    def prefill(self, batch: Dict[str, jnp.ndarray]):
        return self.fam.model_prefill(self.params, batch, self.cfg, self.max_len)

    def decode(self, cache, token, pos, ffn_masks=None, stats=None):
        if (stats is not None and stats.active) or ffn_masks is not None:
            kw = {}
            if ffn_masks is not None:
                kw["ffn_masks"] = ffn_masks
            return self.fam.model_decode(self.params, cache, token, pos,
                                         self.cfg, stats=stats, **kw)
        return self._decode_jit(self.params, cache, token, pos)

    # -- generation with the paper's machinery ------------------------------
    def generate(self, batch: Dict[str, jnp.ndarray], max_new: int,
                 reuse_window: int = 0) -> GenerationResult:
        """Greedy generation. reuse_window=γ enables the paper's Fig. 7c
        strategy: between mask refreshes, only FFN rows already loaded in
        the current window participate (no new weight I/O)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        b, s = tokens.shape
        offset = sp.prompt_token_offset(self.fam, cfg)
        last, cache = self.prefill(batch)
        out: List[np.ndarray] = []
        lps: List[np.ndarray] = []
        tracker = (AggregatedTracker(cfg.n_layers, cfg.d_ff)
                   if self.track and cfg.d_ff else None)
        site_acc: Dict[str, List[float]] = {}
        masks = None

        nxt = jnp.argmax(last[:, : cfg.vocab_size], -1).astype(jnp.int32)
        for step in range(max_new):
            out.append(np.asarray(nxt))
            lp = jax.nn.log_softmax(last[:, : cfg.vocab_size].astype(jnp.float32))
            lps.append(np.asarray(jnp.take_along_axis(lp, nxt[:, None], 1)[:, 0]))
            pos = jnp.full((b,), offset + s + step, jnp.int32)

            need_stats = self.track or (
                reuse_window > 0 and step % max(1, reuse_window) == 0)
            if need_stats:
                stats = cm.StatsCollector(True)
                logits, cache = self.decode(cache, nxt, pos, stats=stats)
                step_masks = _collect_down_act(stats, cfg)
                if tracker is not None and step_masks is not None:
                    tracker.update(step_masks)
                for k, v in stats.stats.items():
                    if k.endswith(("down_in", "up_in", "qkv_in")):
                        site_acc.setdefault(k.split("/")[-1], []).append(float(v))
                if reuse_window > 0 and step_masks is not None:
                    masks = jnp.asarray(step_masks)
            else:
                logits, cache = self.decode(cache, nxt, pos, ffn_masks=masks)
            last = logits
            nxt = jnp.argmax(logits[:, : cfg.vocab_size], -1).astype(jnp.int32)

        sites = {k: float(np.mean(v)) for k, v in site_acc.items()}
        return GenerationResult(tokens=np.stack(out, 1),
                                logprobs=np.stack(lps, 1),
                                site_sparsity=sites, aggregated=tracker,
                                steps=max_new)

    def score(self, batch: Dict[str, jnp.ndarray]) -> float:
        """Mean NLL of batch['tokens'] (perplexity = exp(score))."""
        from repro.train.step import lm_loss
        loss, _ = lm_loss(self.params, batch, self.cfg)
        return float(loss)


def _collect_down_act(stats: cm.StatsCollector, cfg: ModelConfig):
    masks = []
    for i in range(cfg.n_layers):
        key = f"layer{i}/down_act"
        if key in stats.stats:
            masks.append(np.asarray(stats.stats[key]))
    return np.stack(masks) if masks else None
