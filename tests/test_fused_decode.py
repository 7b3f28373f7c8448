"""Fused Pallas decode kernels (ISSUE 7 tentpole): the one-pass
gather-up -> activation -> scatter-down FFN kernel and the in-kernel
block-table paged attention must reproduce the frozen XLA serving path
BYTE-IDENTICALLY at f32 — greedy token streams through
``fast_kernels=True`` equal the frozen-path streams in all three serving
modes (plain γ-window, speculative, predictor), for tiny-relu (GLU) and
tiny-opt (MLP), with chunked prefill composing.

Kernel-level parity is pinned bit-exactly against the unfused Pallas pair
(``sparse_up_matmul`` + ``sparse_matmul_tokens``) — same per-tile dot
shapes, same f32 accumulation order — plus hypothesis properties over the
fixed-capacity tile lists (empty rows, full capacity, duplicated pad
entries revisiting an already-fetched tile exactly once).

Kernels run in interpret mode on CPU (kernels/runtime.resolve_interpret);
the mesh-fallback test runs in a forced-8-device subprocess."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_config
from repro.kernels import fused_decode as kfd
from repro.kernels import paged_attention as kpa
from repro.kernels import sparse_matmul as ksm
from repro.models import common as cm
from repro.models import registry
from repro.predictor.predictors import pack_tile_indices
from repro.serving import ContinuousBatchingEngine
from repro.serving.config import EngineConfig

from subproc import run_forced_devices as _run


# ---------------------------------------------------------------------------
# kernel-level parity: fused == unfused pair, BIT-exact


def _case(T=4, d=64, F=512, tile=128, p=0.5, seed=0):
    n_tiles = F // tile
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(T, d), jnp.float32)
    wg = jnp.asarray(rng.randn(d, F) / np.sqrt(d), jnp.float32)
    wu = jnp.asarray(rng.randn(d, F) / np.sqrt(d), jnp.float32)
    wd = jnp.asarray(rng.randn(F, d) / np.sqrt(F), jnp.float32)
    mask = jnp.asarray(rng.rand(T, n_tiles) < p)
    idx, nvalid = pack_tile_indices(mask, n_tiles)
    return x, wg, wu, wd, idx, nvalid, tile, n_tiles


def _unfused(x, wg, wu, wd, idx, nvalid, tile, unit_mask=None, shift=0.0):
    """The frozen two-kernel lowering the fused kernel replaces."""
    pre = ksm.sparse_up_matmul(x, wg, idx, nvalid, tile=tile)
    hh = jnp.maximum(pre - shift, 0.0)
    if wu is not None:
        hh = hh * ksm.sparse_up_matmul(x, wu, idx, nvalid, tile=tile)
    if unit_mask is not None:
        hh = hh * unit_mask
    y = ksm.sparse_matmul_tokens(hh.astype(wd.dtype), wd, idx, nvalid,
                                 tile=tile)
    return y, hh


def test_fused_matches_unfused_glu():
    x, wg, wu, wd, idx, nvalid, tile, n_tiles = _case()
    y, h = kfd.fused_sparse_ffn(x, wg, wd, idx, nvalid, w_up=wu,
                                activation="relu", tile=tile)
    hh = h
    y0, hh0 = _unfused(x, wg, wu, wd, idx, nvalid, tile)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y0))
    np.testing.assert_array_equal(np.asarray(hh), np.asarray(hh0))


def test_fused_matches_unfused_mlp():
    x, wg, _, wd, idx, nvalid, tile, n_tiles = _case(seed=3)
    y, h = kfd.fused_sparse_ffn(x, wg, wd, idx, nvalid,
                                activation="relu", tile=tile)
    hh = h
    y0, hh0 = _unfused(x, wg, None, wd, idx, nvalid, tile)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y0))
    np.testing.assert_array_equal(np.asarray(hh), np.asarray(hh0))


def test_fused_matches_unfused_masked_and_shifted():
    """The AR-window variant: unit mask applied INSIDE the kernel after the
    GLU multiply, shifted ReLU — both exact (boolean multiply, f32 sub)."""
    x, wg, wu, wd, idx, nvalid, tile, n_tiles = _case(seed=5)
    rng = np.random.RandomState(7)
    eff = jnp.asarray(rng.rand(x.shape[0], wg.shape[1]) < 0.6)
    y, h = kfd.fused_sparse_ffn(x, wg, wd, idx, nvalid, w_up=wu,
                                unit_mask=eff, activation="shifted_relu",
                                shift=0.25, tile=tile)
    hh = h
    y0, hh0 = _unfused(x, wg, wu, wd, idx, nvalid, tile, unit_mask=eff,
                       shift=0.25)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y0))
    np.testing.assert_array_equal(np.asarray(hh), np.asarray(hh0))


# ---------------------------------------------------------------------------
# hypothesis: fixed-capacity tile-list edge cases


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 10 ** 6), st.floats(0.0, 1.0))
def test_fused_tile_list_property(seed, p):
    """Any mask density — including all-empty rows (nvalid == 0 must yield
    exact zeros) and full capacity (== dense) — matches the unfused pair
    bit-exactly, and the scattered h is zero outside selected tiles."""
    x, wg, wu, wd, _, _, tile, n_tiles = _case(T=3, seed=seed % 997)
    rng = np.random.RandomState(seed % 2 ** 31)
    mask = jnp.asarray(rng.rand(3, n_tiles) < p)
    idx, nvalid = pack_tile_indices(mask, n_tiles)
    y, h = kfd.fused_sparse_ffn(x, wg, wd, idx, nvalid, w_up=wu,
                                activation="relu", tile=tile)
    hh = h
    y0, hh0 = _unfused(x, wg, wu, wd, idx, nvalid, tile)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y0))
    np.testing.assert_array_equal(np.asarray(hh), np.asarray(hh0))
    # rows with no live tiles are exactly zero, not epsilon
    empty = ~np.asarray(mask).any(axis=1)
    assert (np.asarray(y)[empty] == 0.0).all()
    assert (np.asarray(hh)[empty] == 0.0).all()
    # h never leaks outside the selected tiles
    units = np.repeat(np.asarray(mask), tile, axis=1)
    assert (np.asarray(hh)[~units] == 0.0).all()


def test_duplicated_pad_tiles_contribute_exactly_once():
    """pack_tile_indices pads by REPEATING the row's first selected tile
    (so padded DMAs revisit an already-fetched block): the kernel must add
    that tile's down-projection exactly once and scatter its h exactly
    once, never per-duplicate."""
    x, wg, wu, wd, _, _, tile, n_tiles = _case(T=2, seed=11)
    # row 0: one live tile + 3 pad duplicates of it; row 1: empty (pads
    # point at tile 0 by construction of top_k on an all-zero mask)
    mask = jnp.zeros((2, n_tiles), bool).at[0, 2].set(True)
    idx, nvalid = pack_tile_indices(mask, n_tiles)
    assert idx[0].tolist() == [2, 2, 2, 2] and nvalid.tolist() == [1, 0]
    y, h = kfd.fused_sparse_ffn(x, wg, wd, idx, nvalid, w_up=wu,
                                activation="relu", tile=tile)
    hh = h
    # single-tile reference, computed directly
    sl = slice(2 * tile, 3 * tile)
    h_ref = (jnp.maximum(x[:1] @ wg[:, sl], 0.0) * (x[:1] @ wu[:, sl]))
    np.testing.assert_array_equal(np.asarray(hh[0, sl]),
                                  np.asarray(h_ref)[0])
    np.testing.assert_array_equal(np.asarray(y[0]),
                                  np.asarray(h_ref @ wd[sl])[0])
    assert (np.asarray(y[1]) == 0.0).all() and (np.asarray(hh[1]) == 0.0).all()


# per-token tile masks over 4 tiles, and the lists' capacity K
_UNION_CASES = {
    "overlapping": ([[1, 1, 0, 0], [0, 1, 1, 0], [1, 0, 1, 1]], 4),
    "disjoint": ([[1, 0, 0, 0], [0, 1, 0, 0], [0, 0, 1, 1]], 4),
    "union_smaller_than_n_tiles": ([[0, 1, 0, 0], [0, 1, 0, 0],
                                    [0, 0, 0, 1]], 4),
    "token_naming_no_tile": ([[0, 0, 0, 0], [1, 0, 1, 0], [0, 0, 1, 0]], 4),
    "no_tile_named": ([[0, 0, 0, 0], [0, 0, 0, 0]], 4),
    "one_token": ([[0, 1, 1, 0]], 4),
    "pads_at_full_capacity": ([[1, 1, 1, 1], [1, 0, 0, 0], [0, 0, 0, 1]],
                              4),
    "truncated_lists": ([[1, 1, 1, 0], [0, 1, 1, 1], [0, 0, 0, 1]], 2),
}


@pytest.mark.parametrize("form", ["predictor", "unit_mask"])
@pytest.mark.parametrize("case", sorted(_UNION_CASES))
def test_union_kernel_matches_per_token_pair(case, form):
    """The kernel walks the batch's UNION of tiles once, all tokens per
    tile, and still equals the per-token unfused pair bit for bit (f32,
    d = 64): a token's rows on tiles other tokens named are exact zeros,
    in h and in y, whether a unit mask is given (the γ path) or not (the
    predictor path); a token naming no tile gets exact zeros."""
    rows, K = _UNION_CASES[case]
    mask = jnp.asarray(rows, bool)
    T, n_tiles = mask.shape
    x, wg, wu, wd, _, _, tile, _ = _case(T=T, seed=sorted(_UNION_CASES)
                                         .index(case))
    idx, nvalid = pack_tile_indices(mask, K)
    glu = form == "unit_mask"
    eff = None
    if form == "unit_mask":
        rng = np.random.RandomState(3)
        eff = jnp.asarray(rng.rand(T, n_tiles * tile) < 0.6)
    y, hh = kfd.fused_sparse_ffn(x, wg, wd, idx, nvalid,
                                 w_up=wu if glu else None, unit_mask=eff,
                                 activation="relu", tile=tile)
    y0, hh0 = _unfused(x, wg, wu if glu else None, wd, idx, nvalid, tile,
                       unit_mask=eff)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y0))
    np.testing.assert_array_equal(np.asarray(hh), np.asarray(hh0))
    # each token's h is zero outside its own (possibly truncated) list
    member, uidx, n_union = kfd.union_tiles(idx, nvalid, n_tiles)
    own = np.zeros((T, n_tiles), bool)
    for t in range(T):
        own[t, np.asarray(idx[t, :nvalid[t]])] = True
    np.testing.assert_array_equal(np.asarray(member), own)
    assert (np.asarray(hh)[~np.repeat(own, tile, axis=1)] == 0.0).all()
    assert (np.asarray(y)[~own.any(axis=1)] == 0.0).all()
    # one ascending list of the union, padded with its last tile
    union = np.flatnonzero(own.any(axis=0)).tolist()
    assert int(n_union) == len(union)
    assert np.asarray(uidx).tolist() == (
        union + [union[-1] if union else 0] * (n_tiles - len(union)))


# ---------------------------------------------------------------------------
# paged attention kernel vs frozen gather-then-attend


@pytest.mark.parametrize("lanes", [16, 128])
@pytest.mark.parametrize("W,window", [(1, 0), (5, 5), (1, 5), (5, 0)])
def test_paged_attention_matches_gathered(W, window, lanes):
    """In-kernel block-table gather off the STACKED pool at a middle layer
    == the same kernel on that layer's slice (bit-exact: same blocks, same
    order) ~= materializing paged_gather + the frozen window_attention, for
    the decode (W=1) and the γ+1 verify window shapes, with and without the
    sliding window, on a pool as wide as the heads and on one padded to a
    128-lane row (zeros past head_dim)."""
    b, kvp, g, hd = 3, 2, 2, 16
    L, n_blocks, bs, nb = 3, 9, 8, 4
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(b, W, kvp, g, hd), jnp.float32)
    pad = ((0, 0),) * 4 + ((0, lanes - hd),)
    kp = jnp.pad(jnp.asarray(rng.randn(L, n_blocks, kvp, bs, hd),
                             jnp.float32), pad)
    vp = jnp.pad(jnp.asarray(rng.randn(L, n_blocks, kvp, bs, hd),
                             jnp.float32), pad)
    table = jnp.asarray(rng.randint(1, n_blocks, (b, nb)), jnp.int32)
    pos = (jnp.asarray(rng.randint(W - 1, nb * bs, (b,)), jnp.int32)[:, None]
           + jnp.arange(-W + 1, 1, dtype=jnp.int32)[None, :])
    layer = 1
    got = kpa.paged_window_attention(q, kp, vp, table, pos, jnp.int32(layer),
                                     window=window)
    sliced = kpa.paged_window_attention(q, kp[layer][None], vp[layer][None],
                                        table, pos, 0, window=window)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(sliced))
    kg = cm.paged_gather(kp[layer, ..., :hd], table)
    vg = cm.paged_gather(vp[layer, ..., :hd], table)
    want = cm.window_attention(q, kg, vg, pos, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-6, atol=2e-6)


def _write_case(case):
    """(table, pos, enable) for the writer parity cases; block 0 is the
    scratch block, bs = 16 (one bf16 row group per block)."""
    if case == "decode_idle_slots":
        # W = 1; slots 1 and 3 idle (all-scratch tables), so two tokens
        # land in the scratch block's row 0
        table = [[1, 2, 0], [0, 0, 0], [3, 4, 5], [0, 0, 0]]
        pos = [[17], [0], [40], [0]]
        enable = None
    else:
        # W = 5: slot 0 crosses from block 2 into block 3 with its last
        # two tokens disabled; slot 1's five tokens share one block; slot 2
        # is idle — every disabled token goes to the scratch block, several
        # at the same row
        table = [[1, 2, 3], [4, 5, 6], [0, 0, 0]]
        pos = [[29, 30, 31, 32, 33], [3, 4, 5, 6, 7], [0, 1, 2, 3, 4]]
        enable = [[1, 1, 1, 0, 0], [1, 1, 1, 1, 1], [0, 0, 0, 0, 0]]
    table = jnp.asarray(table, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    enable = None if enable is None else jnp.asarray(enable, bool)
    return table, pos, enable


@pytest.mark.parametrize("lanes", [16, 128])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", ["decode_idle_slots", "window_disabled"])
def test_paged_kv_write_matches_scatter(case, dtype, lanes):
    """The aliased Pallas writer stores the same rows at the same places as
    the frozen XLA scatter, bit for bit over the whole stacked pool:
    disabled window tokens and idle slots in the scratch block, several
    tokens of one slot in one block, other layers untouched; into a pool as
    wide as the heads, or one padded to a 128-lane row (the rows' zeros
    past head_dim written too)."""
    table, pos, enable = _write_case(case)
    b, W = pos.shape
    L, n_blocks, kvp, bs, hd = 3, 7, 2, 16, 16
    rng = np.random.RandomState(4)
    pages = jnp.asarray(rng.randn(L, n_blocks, kvp, bs, lanes), dtype)
    val = jnp.asarray(rng.randn(b, W, kvp, hd), dtype)
    layer = jnp.int32(1)
    want = cm.paged_write_window(
        pages, layer, table, pos,
        jnp.pad(val, ((0, 0),) * 3 + ((0, lanes - hd),)), bs, enable)
    got = cm.paged_write_window(pages, layer, table, pos, val, bs, enable,
                                fast_kernels=True)
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                  np.asarray(want.astype(jnp.float32)))
    if enable is None:  # the W = 1 entry point routes the same way
        got1 = cm.paged_write_token(pages, layer, table, pos[:, 0],
                                    val[:, 0], bs, fast_kernels=True)
        np.testing.assert_array_equal(np.asarray(got1.astype(jnp.float32)),
                                      np.asarray(want.astype(jnp.float32)))


# ---------------------------------------------------------------------------
# serving-level: f32 greedy streams byte-identical, fast vs frozen


def _setup(name):
    cfg = get_config(name).replace(compute_dtype="float32")
    fam = registry.get_family(cfg)
    params = fam.init_params(jax.random.PRNGKey(0), cfg)
    rng_prompts = [(1, 9), (2, 5), (3, 13)]
    prompts = [np.random.RandomState(s).randint(
                   0, cfg.vocab_size, ln).astype(np.int32)
               for s, ln in rng_prompts]
    return cfg, fam, params, prompts


def _serve(cfg, params, prompts, max_new=8, **kw):
    eng = ContinuousBatchingEngine(cfg, params, n_slots=2, block_size=8,
                                   max_blocks_per_seq=6, **kw)
    uids = [eng.submit(p, max_new) for p in prompts]
    res = eng.run()
    return [res[u].tokens.tolist() for u in uids], eng


@pytest.mark.parametrize("name", ["tiny-relu", "tiny-opt"])
def test_plain_mode_fast_kernels_byte_identical(name):
    cfg, fam, params, prompts = _setup(name)
    base, e0 = _serve(cfg, params, prompts, fast_kernels=False)
    got, e1 = _serve(cfg, params, prompts, fast_kernels=True)
    assert got == base, (name, base, got)
    assert not e0.fast_kernels and e1.fast_kernels
    # chunked prefill lowers through the same fast window step
    gotc, _ = _serve(cfg, params, prompts, fast_kernels=True, prefill_chunk=4)
    assert gotc == base, (name, "chunked", base, gotc)
    # the fast AR path reads all three (GLU) / both (MLP) projections
    # sparsely — the accounting scope widens accordingly
    n_all = 3 if cfg.ffn_kind == "glu" else 2
    assert e1.weight_io_bytes_per_step() == pytest.approx(
        n_all * e0.weight_io_bytes_per_step())


@pytest.mark.parametrize("name", ["tiny-relu", "tiny-opt"])
def test_speculative_mode_fast_kernels_byte_identical(name):
    cfg, fam, params, prompts = _setup(name)
    dcfg = cfg.replace(name=cfg.name + "-draft", n_layers=1)
    dparams = fam.init_params(jax.random.PRNGKey(2), dcfg)
    kw = dict(draft_cfg=dcfg, draft_params=dparams, gamma=4)
    base, e0 = _serve(cfg, params, prompts, fast_kernels=False, **kw)
    got, e1 = _serve(cfg, params, prompts, fast_kernels=True, **kw)
    assert got == base, (name, base, got)
    # same windows verified -> same acceptance telemetry
    assert abs(e1.s_agg_window() - e0.s_agg_window()) < 1e-9


@pytest.mark.parametrize("name", ["tiny-relu", "tiny-opt"])
def test_predictor_mode_fast_kernels_byte_identical(name):
    from repro.predictor import calibrate_from_config
    cfg, fam, params, prompts = _setup(name)
    cfg = cfg.replace_sparsity(predictor="sign", predictor_recall=1.0)
    calib = {"tokens": jax.random.randint(jax.random.PRNGKey(7), (4, 32),
                                          0, cfg.vocab_size)}
    pred = calibrate_from_config(params, cfg, calib, tile=1)
    base, e0 = _serve(cfg, params, prompts, predictor=pred,
                      fast_kernels=False)
    got, e1 = _serve(cfg, params, prompts, predictor=pred,
                     fast_kernels=True)
    assert got == base, (name, base, got)
    # identical gathered tiles -> identical measured density and savings
    assert abs(e1.weight_io_saved() - e0.weight_io_saved()) < 1e-9
    assert e1.predictor_recall() == e0.predictor_recall()


_FFN_COUNTERS = ("repro_ffn_tiles_fetched_total",
                 "repro_ffn_tile_token_pairs_total")


def _spy_ffn_tiles(eng, seen):
    """Record (active slots, tiles-fetched increment, tile-token-pair
    increment) for every decode step the engine counts."""
    reg, inner = eng.obs.registry, eng._count_ffn_tiles

    def count(out_np, active):
        before = [reg.get(n).value() for n in _FFN_COUNTERS]
        nxt = inner(out_np, active)
        after = [reg.get(n).value() for n in _FFN_COUNTERS]
        seen.append((len(active), after[0] - before[0],
                     after[1] - before[1]))
        return nxt
    eng._count_ffn_tiles = count


@pytest.mark.parametrize("reuse", ["refresh_every_token", "sparse_union"])
def test_ffn_tile_counters(reuse):
    """The fused kernel's tile counters, counted in-graph and fetched with
    the tokens: with a refresh on every token each decode step fetches
    every tile of every layer once, and each active slot names all of
    them; under γ-masks with a sparse union the tiles fetched are that
    union, counted here on the host from the masks the step reads."""
    cfg, fam, params, prompts = _setup("tiny-opt")
    L, tile = cfg.n_layers, cm.ffn_gather_tile(cfg)
    n_tiles = cfg.d_ff // tile
    eng = ContinuousBatchingEngine(cfg, params, config=EngineConfig(
        n_slots=2, block_size=8, max_blocks_per_seq=6, fast_kernels=True))
    seen = []
    _spy_ffn_tiles(eng, seen)
    if reuse == "refresh_every_token":
        for p in prompts:
            eng.submit(p, 6)
        eng.run()
        assert any(active == 2 for active, _, _ in seen)
        for active, fetched, pairs in seen:
            assert fetched == L * n_tiles
            assert pairs == L * n_tiles * active
        return
    # γ-reuse: after the first (refresh) step both slots ride their masks;
    # hand them masks whose union is one tile of each layer
    for p in prompts[:2]:
        eng.submit(p, 6, reuse_window=100)
    eng.step()
    masks = np.zeros((L, 2, cfg.d_ff), bool)
    for li in range(L):
        t = li % n_tiles
        masks[li, 0, t * tile:t * tile + 3] = True
        masks[li, 1, t * tile + 5] = True
    eng.masks = jnp.asarray(masks)
    seen.clear()
    eng.step()
    named = masks.reshape(L, 2, n_tiles, tile).any(axis=-1)  # (L, B, tiles)
    union = named.any(axis=1).sum()
    assert union < L * n_tiles
    assert seen == [(2, union, named.sum())]


def test_fast_kernels_autodetect_off_on_cpu():
    """Default (fast_kernels=None) resolves from the backend: off on CPU,
    so CI keeps the frozen XLA paths unless a test opts in."""
    cfg, fam, params, prompts = _setup("tiny-relu")
    _, eng = _serve(cfg, params, prompts[:1], max_new=2)
    assert eng.fast_kernels == (jax.default_backend() != "cpu")


def test_mesh_forces_fallback_with_warning():
    """GSPMD cannot partition pallas_call: under a mesh the engine must
    warn, force fast_kernels=False, and stream identically."""
    out = _run("""
    import warnings
    import jax, numpy as np
    from repro.configs import get_config
    from repro.models import registry
    from repro.launch.mesh import make_host_mesh
    from repro.serving import ContinuousBatchingEngine

    cfg = get_config("tiny-relu").replace(compute_dtype="float32")
    params = registry.get_family(cfg).init_params(jax.random.PRNGKey(0), cfg)
    prompts = [np.random.RandomState(s).randint(
                   0, cfg.vocab_size, ln).astype(np.int32)
               for s, ln in ((1, 9), (2, 5))]

    def serve(**kw):
        eng = ContinuousBatchingEngine(cfg, params, n_slots=2, block_size=8,
                                       max_blocks_per_seq=6, **kw)
        uids = [eng.submit(p, 8) for p in prompts]
        res = eng.run()
        return [res[u].tokens.tolist() for u in uids], eng

    base, _ = serve(fast_kernels=False)
    mesh = make_host_mesh(1, 8)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        got, eng = serve(fast_kernels=True, mesh=mesh)
    assert eng.fast_kernels is False
    assert any("fast_kernels" in str(x.message) for x in w), \\
        [str(x.message) for x in w]
    assert got == base, (base, got)
    print("OK")
    """)
    assert "OK" in out
