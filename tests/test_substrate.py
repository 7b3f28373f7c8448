"""Substrate tests: data pipeline determinism/resume, checkpoint atomicity +
elastic restore, trainer resume, gradient compression convergence, FLOPs
accounting vs the paper's Table-1 numbers."""
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs import TrainConfig, get_config
from repro.configs.base import ModelConfig
from repro.core import flops as flops_lib
from repro.data.pipeline import DataConfig, IteratorState, PackedIterator
from repro.models import registry
from repro.optim import adamw, compression
from repro.train.loop import Trainer


def test_data_determinism_and_resume():
    dc = DataConfig(batch_size=2, seq_len=32)
    it1 = PackedIterator(dc)
    b1 = [next(it1) for _ in range(3)]
    state = it1.state()
    b_next = next(it1)

    it2 = PackedIterator(dc)
    b2 = [next(it2) for _ in range(3)]
    for a, b in zip(b1, b2):
        np.testing.assert_array_equal(a["tokens"], b["tokens"])

    it3 = PackedIterator(dc, state)
    b3 = next(it3)
    # resumed iterator consumes the same docs (carry buffer differs, so the
    # doc id stream must match)
    assert it3.state().next_doc >= state.next_doc


def test_data_host_sharding_disjoint():
    dc0 = DataConfig(batch_size=1, seq_len=64, host_index=0, host_count=2)
    dc1 = DataConfig(batch_size=1, seq_len=64, host_index=1, host_count=2)
    it0, it1 = PackedIterator(dc0), PackedIterator(dc1)
    next(it0), next(it1)
    assert it0.next_doc % 2 == 0 and it1.next_doc % 2 == 1


def test_checkpoint_roundtrip_and_keep(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": [jnp.ones((4,), jnp.int32)]}
    for s in (10, 20, 30):
        mgr.save(s, tree, extras={"step": s, "data": {"next_doc": s}})
    assert mgr.all_steps() == [20, 30]  # keep=2 GC
    got, extras = mgr.restore(tree)
    np.testing.assert_array_equal(np.asarray(got["a"]), np.asarray(tree["a"]))
    assert extras["step"] == 30


def test_checkpoint_elastic_reshard(tmp_path):
    """Save under one layout, restore with explicit target shardings."""
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    tree = {"w": jnp.arange(32, dtype=jnp.float32).reshape(4, 8)}
    mgr.save(1, tree, extras={"step": 1})
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    from jax.sharding import NamedSharding, PartitionSpec as P
    sh = {"w": NamedSharding(mesh, P("data", None))}
    got, _ = mgr.restore(tree, shardings=sh)
    np.testing.assert_array_equal(np.asarray(got["w"]), np.asarray(tree["w"]))
    assert got["w"].sharding == sh["w"]


def test_trainer_runs_and_resumes(tmp_path):
    cfg = get_config("tiny-relu")
    tc = TrainConfig(learning_rate=3e-3, total_steps=8, warmup_steps=2,
                     num_microbatches=1, remat_policy="none", seed=0)
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, batch_size=4)
    tr = Trainer(cfg, tc, dc, ckpt_dir=str(tmp_path), ckpt_every=4,
                 eval_every=100, log=lambda *_: None)
    rep = tr.run(6)
    assert rep.steps == 6
    assert np.isfinite(rep.losses).all()

    # simulate restart: a fresh trainer must resume from the checkpoint
    tr2 = Trainer(cfg, tc, dc, ckpt_dir=str(tmp_path), ckpt_every=4,
                  eval_every=100, log=lambda *_: None)
    rep2 = tr2.run(8)
    assert rep2.resumed_from == 6
    assert rep2.steps == 2


def test_loss_decreases_tiny():
    cfg = get_config("tiny-relu")
    tc = TrainConfig(learning_rate=5e-3, total_steps=30, warmup_steps=3,
                     schedule="constant", num_microbatches=1,
                     remat_policy="none")
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=48, batch_size=8)
    tr = Trainer(cfg, tc, dc, log=lambda *_: None)
    rep = tr.run(30)
    assert np.mean(rep.losses[-5:]) < np.mean(rep.losses[:5]) - 0.1


def test_int8_ef_compression_roundtrip():
    x = jnp.asarray(np.random.RandomState(0).randn(64, 32), jnp.float32)
    q, s = compression.quantize_int8(x)
    err = np.abs(np.asarray(compression.dequantize_int8(q, s)) - np.asarray(x))
    assert err.max() <= float(s) * 0.51 + 1e-6


def test_ddp_compressed_matches_uncompressed_direction():
    """int8-EF DDP step loss should track the uncompressed step closely."""
    from repro.train.ddp import make_ddp_train_step
    cfg = get_config("tiny-relu")
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, batch_size=4)
    it = PackedIterator(dc)
    fam = registry.get_family(cfg)
    params = fam.init_params(jax.random.PRNGKey(0), cfg)

    losses = {}
    for comp in ("none", "int8_ef"):
        tc = TrainConfig(learning_rate=5e-3, total_steps=10, warmup_steps=1,
                         schedule="constant", grad_compression=comp)
        step = make_ddp_train_step(cfg, tc, mesh)
        p = jax.tree.map(jnp.copy, params)
        opt = adamw.init_opt_state(p)
        ef = compression.init_ef_state(p)
        it2 = PackedIterator(dc)
        ls = []
        for _ in range(8):
            batch = {k: jnp.asarray(v) for k, v in next(it2).items()}
            p, opt, ef, m = step(p, opt, ef, batch)
            ls.append(float(m["loss"]))
        losses[comp] = ls
    # both decrease, and end within 10% of each other
    for comp in losses:
        assert losses[comp][-1] < losses[comp][0]
    assert abs(losses["int8_ef"][-1] - losses["none"][-1]) < 0.1 * losses["none"][-1] + 0.2


def test_table1_flops_reproduction():
    """The analytic accounting reproduces the paper's Table-1 MACs/token."""
    opt67 = get_config("opt-6.7b")
    dense = flops_lib.macs_per_token(opt67) / 1e9
    assert abs(dense - 6.6) < 0.3  # paper: 6.6 G
    s1 = flops_lib.macs_per_token(
        opt67, flops_lib.SparsityLevels(down=0.97)) / 1e9
    assert abs(s1 - 4.5) < 0.3  # paper: 4.5 G
    s2 = flops_lib.macs_per_token(
        opt67, flops_lib.SparsityLevels(qkv=0.5, up=0.40, down=0.97)) / 1e9
    assert abs(s2 - 2.8) < 0.3  # paper: 2.8 G

    falcon = get_config("falcon-7b")
    fd = flops_lib.macs_per_token(falcon) / 1e9
    assert abs(fd - 6.6) < 0.5  # paper: 6.6 G
    f2 = flops_lib.macs_per_token(
        falcon, flops_lib.SparsityLevels(qkv=0.56, up=0.56, down=0.95)) / 1e9
    assert abs(f2 - 2.2) < 0.4  # paper: 2.2 G

    llama = get_config("llama-7b")
    ld = flops_lib.macs_per_token(llama) / 1e9
    assert abs(ld - 6.6) < 0.5  # paper: 6.6 G
    l2 = flops_lib.macs_per_token(
        llama, flops_lib.SparsityLevels(qkv=0.51, up=0.67, down=0.65)) / 1e9
    assert abs(l2 - 2.9) < 0.5  # paper: 2.9 G
