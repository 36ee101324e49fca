"""Distributed substrate tests: sharding rules, optimizer, checkpointing,
fault tolerance, gradient compression.  Mesh-shape logic is tested with an
AbstractMesh (no devices needed)."""

import dataclasses
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ARCHS, reduced
from repro.distributed.compression import (
    compressed_grads,
    init_error_feedback,
    int8_compress,
    int8_decompress,
    topk_compress,
    topk_decompress,
)
from repro.distributed.checkpoint import load_checkpoint, save_checkpoint
from repro.distributed.fault_tolerance import (
    StragglerMonitor,
    remesh_plan,
    should_checkpoint,
)
from repro.distributed.sharding import (
    batch_pspecs,
    cache_pspecs,
    param_pspecs,
    safe_pspec,
)
from repro.launch.specs import abstract_params, input_specs
from repro.configs.base import SHAPES

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _axis_size(s, mesh):
    axes = s if isinstance(s, tuple) else (s,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


@pytest.mark.parametrize("arch_id", sorted(ARCHS))
def test_param_specs_divide_production_mesh(arch_id):
    """Every parameter PartitionSpec must divide at full production scale
    (after the divisibility guard)."""
    cfg = ARCHS[arch_id]
    params = abstract_params(cfg)
    specs = param_pspecs(params)

    def check(leaf, spec):
        guarded = safe_pspec(spec, leaf.shape, MESH)
        for ax, s in enumerate(guarded):
            if s is not None:
                assert leaf.shape[ax] % _axis_size(s, MESH) == 0

    jax.tree.map(check, params, specs, is_leaf=lambda x: isinstance(x, P))


def test_param_specs_shard_the_big_leaves():
    """The guard must not silently replicate the dominant parameters."""
    cfg = ARCHS["granite-8b"]
    params = abstract_params(cfg)
    specs = param_pspecs(params)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    sflat = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    for (path, leaf), spec in zip(flat, sflat):
        guarded = safe_pspec(spec, leaf.shape, MESH)
        if leaf.size * 4 > 64 * 2**20:  # every leaf > 64 MB must be sharded
            assert any(s is not None for s in guarded), (path, leaf.shape)


def test_cache_specs_divide(rng=None):
    for arch_id in ("granite-8b", "jamba-v0.1-52b", "deepseek-v2-236b", "xlstm-1.3b"):
        cfg = ARCHS[arch_id]
        specs = input_specs(cfg, SHAPES["decode_32k"])
        cspecs = cache_pspecs(specs["cache"], MESH)

        def check(leaf, spec):
            for ax, s in enumerate(spec):
                if s is not None:
                    assert leaf.shape[ax] % _axis_size(s, MESH) == 0, (arch_id, leaf.shape, spec)

        jax.tree.map(check, specs["cache"], cspecs, is_leaf=lambda x: isinstance(x, P))


def test_batch_specs_replicate_unshardable_batch():
    batch = {"tokens": jax.ShapeDtypeStruct((1, 1), jnp.int32)}
    specs = batch_pspecs(batch, MESH)
    assert specs["tokens"] == P(None, None)
    batch = {"tokens": jax.ShapeDtypeStruct((256, 4096), jnp.int32)}
    specs = batch_pspecs(batch, MESH)
    assert specs["tokens"][0] in ("data", ("data",))


def test_safe_pspec_multipod():
    s = safe_pspec(P(("pod", "data"), None), (32, 128), MESH3)
    assert s == P(("pod", "data"), None)
    s = safe_pspec(P(("pod", "data"), None), (16, 128), MESH3)
    assert s == P(None, None)


# ---------------------------------------------------------------------------
# optimizer + training loop behaviour
# ---------------------------------------------------------------------------
def test_training_reduces_loss():
    from repro.training.optimizer import OptConfig
    from repro.training.train_step import init_train_state, make_train_step

    cfg = dataclasses.replace(
        reduced(ARCHS["granite-8b"], periods=1), vocab_size=64, remat=False
    )
    params, opt = init_train_state(jax.random.PRNGKey(0), cfg)
    step = jax.jit(make_train_step(cfg, OptConfig(learning_rate=1e-2, warmup_steps=2, total_steps=60)))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size)
    batch = {"tokens": tokens}  # overfit one batch
    losses = []
    for _ in range(25):
        params, opt, m = step(params, opt, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.7, losses[:3] + losses[-3:]
    assert int(opt["step"]) == 25


def test_microbatching_matches_full_batch():
    from repro.training.optimizer import OptConfig
    from repro.training.train_step import init_train_state, make_train_step

    cfg = dataclasses.replace(
        reduced(ARCHS["qwen2.5-3b"], periods=1),
        vocab_size=64, remat=False, compute_dtype="float32",
    )
    params, opt = init_train_state(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab_size)}
    oc = OptConfig(warmup_steps=1, total_steps=10)
    p1, _, m1 = make_train_step(cfg, oc, microbatches=1)(params, opt, batch)
    p2, _, m2 = make_train_step(cfg, oc, microbatches=4)(params, opt, batch)
    # same gradients up to accumulation-order rounding
    err = max(
        float(jnp.max(jnp.abs(a - b)))
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2))
    )
    assert err < 2e-5, err


# ---------------------------------------------------------------------------
# checkpoint/restart
# ---------------------------------------------------------------------------
def test_checkpoint_roundtrip_bitexact(tmp_path: pathlib.Path):
    from repro.training.optimizer import OptConfig
    from repro.training.train_step import init_train_state, make_train_step

    cfg = dataclasses.replace(reduced(ARCHS["qwen2.5-3b"], periods=1), vocab_size=64, remat=False)
    params, opt = init_train_state(jax.random.PRNGKey(0), cfg)
    step = jax.jit(make_train_step(cfg, OptConfig(warmup_steps=1, total_steps=50)))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0, cfg.vocab_size)}

    # run 3 steps, checkpoint, run 2 more -> reference
    for _ in range(3):
        params, opt, _ = step(params, opt, batch)
    save_checkpoint(tmp_path / "ck", 3, params=params, opt_state=opt)
    ref_params, ref_opt = params, opt
    for _ in range(2):
        ref_params, ref_opt, _ = step(ref_params, ref_opt, batch)

    # "crash", restore, continue -> must be bit-exact
    step_no, trees = load_checkpoint(tmp_path / "ck", params=params, opt_state=opt)
    assert step_no == 3
    r_params, r_opt = trees["params"], trees["opt_state"]
    for _ in range(2):
        r_params, r_opt, _ = step(r_params, r_opt, batch)
    for a, b in zip(jax.tree.leaves(ref_params), jax.tree.leaves(r_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# fault tolerance + compression
# ---------------------------------------------------------------------------
def test_straggler_detection():
    mon = StragglerMonitor()
    for s in range(6):
        for w in range(8):
            mon.record(f"w{w}", s, 1.0 if w else 3.5)  # w0 is slow
    assert mon.stragglers(threshold=2.0) == ["w0"]
    assert mon.dead(current_step=10) == [f"w{i}" for i in range(8)]
    assert should_checkpoint(7, every=100, alarms=["w0"])
    assert should_checkpoint(200, every=100, alarms=[])
    assert not should_checkpoint(7, every=100, alarms=[])


def test_remesh_preserves_model_axis():
    plan = remesh_plan(alive_devices=240, old_shape=(16, 16))
    assert plan.shape == (15, 16)
    assert not plan.reshard_model_axis
    assert plan.devices_used == 240
    assert plan.batch_scale == pytest.approx(15 / 16)


def test_remesh_degraded_mode():
    plan = remesh_plan(alive_devices=12, old_shape=(16, 16))
    assert plan.reshard_model_axis
    assert plan.shape == (1, 8) or plan.shape[-1] == 8


def test_remesh_multipod():
    plan = remesh_plan(alive_devices=384, old_shape=(2, 16, 16),
                       axis_names=("pod", "data", "model"))
    assert plan.shape[-1] == 16
    assert plan.devices_used <= 384
    assert not plan.reshard_model_axis


def test_topk_compression_roundtrip():
    g = jnp.array([0.0, 5.0, -3.0, 0.1, 0.01, 2.0])
    vals, idx = topk_compress(g, ratio=0.5)
    rec = topk_decompress(vals, idx, g.shape)
    np.testing.assert_allclose(np.sort(np.abs(np.asarray(rec)))[-3:], [2.0, 3.0, 5.0])


def test_int8_compression_error_bounded():
    g = jax.random.normal(jax.random.PRNGKey(0), (128,))
    q, s = int8_compress(g)
    rec = int8_decompress(q, s)
    assert float(jnp.max(jnp.abs(rec - g))) <= float(s) * 0.5 + 1e-6


def test_error_feedback_preserves_signal():
    """With error feedback, repeated compression passes the full gradient
    through over time (sum of effective grads ~ sum of true grads)."""
    g = {"w": jax.random.normal(jax.random.PRNGKey(0), (64,))}
    ef = init_error_feedback(g)
    total = jnp.zeros((64,))
    T = 50
    for _ in range(T):
        eff, ef, ratio = compressed_grads(g, ef, method="topk", ratio=0.1)
        total = total + eff["w"]
    # exact telescoping identity of error feedback: transmitted = T*g - e_T
    np.testing.assert_allclose(
        np.asarray(total),
        T * np.asarray(g["w"]) - np.asarray(ef["w"]),
        rtol=1e-4, atol=1e-4,
    )
    # the dominant half of the gradient mass is transmitted near-exactly
    gw = np.abs(np.asarray(g["w"]))
    big = gw >= np.median(gw)
    err = np.abs(np.asarray(total / T) - np.asarray(g["w"]))
    assert (err[big] <= gw[big] * 0.35 + 1e-3).all()
    assert ratio == pytest.approx(0.2)
