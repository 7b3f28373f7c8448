"""Distribution tests that need multiple (fake) devices — run in
subprocesses so the main pytest process keeps its single-device view."""
from subproc import run_forced_devices as _run


def test_ddp_shard_map_8dev():
    """shard_map DDP step with int8-EF compression on 8 fake devices."""
    out = _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config, TrainConfig
    from repro.data.pipeline import DataConfig, PackedIterator
    from repro.models import registry
    from repro.optim import adamw, compression
    from repro.train.ddp import make_ddp_train_step
    mesh = jax.make_mesh((8,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    cfg = get_config("tiny-relu")
    fam = registry.get_family(cfg)
    params = fam.init_params(jax.random.PRNGKey(0), cfg)
    tc = TrainConfig(learning_rate=5e-3, total_steps=6, warmup_steps=1,
                     schedule="constant", grad_compression="int8_ef")
    step = make_ddp_train_step(cfg, tc, mesh)
    opt = adamw.init_opt_state(params)
    ef = compression.init_ef_state(params)
    it = PackedIterator(DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                                   batch_size=8))
    losses = []
    for _ in range(4):
        b = {k: jnp.asarray(v) for k, v in next(it).items()}
        params, opt, ef, m = step(params, opt, ef, b)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses
    print("OK", losses[0], losses[-1])
    """)
    assert "OK" in out


def test_elastic_checkpoint_reshard_8_to_4():
    """Checkpoint written under an 8-device mesh restores onto 4 devices."""
    out = _run("""
    import tempfile, jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.checkpoint.manager import CheckpointManager
    d = tempfile.mkdtemp()
    mesh8 = jax.make_mesh((8,), ("data",),
                          axis_types=(jax.sharding.AxisType.Auto,))
    w = jax.device_put(jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
                       NamedSharding(mesh8, P("data", None)))
    mgr = CheckpointManager(d, async_save=False)
    mgr.save(5, {"w": w}, extras={"step": 5})
    # restore onto a DIFFERENT (4-device) mesh
    mesh4 = jax.make_mesh((4,), ("data",),
                          axis_types=(jax.sharding.AxisType.Auto,),
                          devices=jax.devices()[:4])
    sh4 = {"w": NamedSharding(mesh4, P("data", None))}
    got, extras = mgr.restore({"w": w}, shardings=sh4)
    np.testing.assert_array_equal(np.asarray(got["w"]), np.asarray(w))
    assert got["w"].sharding == sh4["w"]
    assert extras["step"] == 5
    print("OK")
    """)
    assert "OK" in out


def test_tiny_pjit_train_on_4x2_mesh():
    """The production train step (FSDP+TP rules) on a tiny 4x2 mesh: loss is
    finite and params shard according to the rules."""
    out = _run("""
    import jax, jax.numpy as jnp
    from repro.configs import get_config, TrainConfig
    from repro.configs.base import ShapeConfig
    from repro.launch import specs as specs_lib
    from repro.models import registry
    from repro.optim import adamw
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = get_config("tiny-relu").replace(d_ff=256, vocab_size=512)
    shape = ShapeConfig("t", "train", 64, 8, num_microbatches=2)
    tc = TrainConfig(learning_rate=1e-3, num_microbatches=2,
                     remat_policy="minimal", total_steps=4, warmup_steps=1)
    with mesh:
        jitted, (pshape, oshape, bshape) = specs_lib.build_train(
            cfg, shape, mesh, tc)
        fam = registry.get_family(cfg)
        params = fam.init_params(jax.random.PRNGKey(0), cfg)
        opt = adamw.init_opt_state(params)
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 64),
                                              0, cfg.vocab_size)}
        p2, o2, m = jitted(params, opt, batch)
    import numpy as np
    assert np.isfinite(float(m["loss"]))
    # FFN weights must actually be sharded over (data, model)
    wd = p2["layers"]["ffn"]["wd"]
    assert len(wd.sharding.device_set) == 8
    print("OK", float(m["loss"]))
    """, devices=8)
    assert "OK" in out


def test_flash_decode_seq_sharded_cache():
    """decode_attention over a sequence-sharded cache == unsharded result
    (GSPMD partial-softmax correctness)."""
    out = _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.models import common as cm
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rng = np.random.RandomState(0)
    b, S, kvp, g, d = 2, 32, 2, 2, 8
    q = jnp.asarray(rng.randn(b, kvp, g, d), jnp.float32)
    # head-major layout (b, kvp, S, d); S sharded over "model"
    kc = jnp.asarray(rng.randn(b, kvp, S, d), jnp.float32)
    vc = jnp.asarray(rng.randn(b, kvp, S, d), jnp.float32)
    pos = jnp.asarray([20, 20], jnp.int32)
    want = cm.decode_attention(q, kc, vc, pos)
    csh = NamedSharding(mesh, P("data", None, "model", None))
    with mesh:
        fn = jax.jit(cm.decode_attention,
                     in_shardings=(NamedSharding(mesh, P("data")), csh, csh,
                                   NamedSharding(mesh, P("data"))),
                     static_argnames=())
        got = fn(q, kc, vc, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    print("OK")
    """)
    assert "OK" in out
