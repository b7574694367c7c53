"""Optimizer, data pipeline, checkpoint/restart, compression, elasticity."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import checkpoint as ckpt_lib
from repro import configs
from repro.configs.shapes import ShapeCell
from repro.data.pipeline import DataLoader
from repro.distributed.compression import (compressed_psum_tree,
                                           init_error_state)
from repro.optim import AdamW, constant_schedule, cosine_schedule


def test_adamw_quadratic_convergence():
    opt = AdamW(constant_schedule(0.1), weight_decay=0.0)
    params = {"w": jnp.array([5.0, -3.0])}
    state = opt.init(params)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}        # d/dw ||w||²
        params, state, m = opt.update(grads, state, params)
    assert float(jnp.abs(params["w"]).max()) < 1e-2


def test_adamw_clip():
    opt = AdamW(constant_schedule(0.1), clip_norm=1.0, weight_decay=0.0)
    params = {"w": jnp.zeros(3)}
    state = opt.init(params)
    _, _, metrics = opt.update({"w": jnp.full(3, 100.0)}, state, params)
    assert float(metrics["grad_norm"]) > 100.0   # norm reported pre-clip


def test_cosine_schedule_shape():
    f = cosine_schedule(1.0, warmup_steps=10, total_steps=100)
    assert float(f(0)) == 0.0
    assert abs(float(f(10)) - 1.0) < 1e-6
    assert float(f(100)) <= 0.11
    assert float(f(5)) == pytest.approx(0.5)


def test_bf16_moments_update():
    opt = AdamW(constant_schedule(0.01), moment_dtype=jnp.bfloat16)
    params = {"w": jnp.ones(4)}
    state = opt.init(params)
    assert state.m["w"].dtype == jnp.bfloat16
    p2, s2, _ = opt.update({"w": jnp.ones(4)}, state, params)
    assert bool(jnp.all(p2["w"] < params["w"]))


def test_loader_determinism_and_cursor():
    cfg = configs.get_reduced("tinyllama-1.1b")
    cell = ShapeCell("t", "train", 32, 4)
    l1 = DataLoader(cfg, cell, 2, seed=7)
    b0, b1 = l1.make_batch(0), l1.make_batch(1)
    l2 = DataLoader(cfg, cell, 2, seed=7)
    np.testing.assert_array_equal(b0["labels"], l2.make_batch(0)["labels"])
    # cursor restore replays the same stream
    l2.restore({"seed": 7, "step": 1})
    it = iter(l2)
    nxt = next(it)
    np.testing.assert_array_equal(nxt["labels"], b1["labels"])
    l2.stop()


def test_checkpoint_roundtrip(tmp_path):
    state = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
             "nested": {"b": jnp.ones(4, jnp.bfloat16)}}
    ckpt_lib.save(str(tmp_path), 42, state, extras={"loader": {"x": 1}})
    like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        state)
    restored, extras, step = ckpt_lib.restore(str(tmp_path), like)
    assert step == 42 and extras["loader"]["x"] == 1
    np.testing.assert_array_equal(restored["a"], state["a"])
    assert restored["nested"]["b"].dtype == jnp.bfloat16


def test_checkpoint_gc_and_latest(tmp_path):
    s = {"a": jnp.zeros(2)}
    for step in (1, 2, 3, 4, 5):
        ckpt_lib.save(str(tmp_path), step, s, keep=2)
    assert ckpt_lib.latest_step(str(tmp_path)) == 5
    tags = [t for t in os.listdir(tmp_path) if t.startswith("step_")]
    assert len(tags) == 2


def test_train_restart_equals_continuous(tmp_path):
    """Fault tolerance: (train 6) == (train 3, crash, restore, train 3)."""
    from repro.launch.train import build
    from repro.training.loop import LoopConfig, Trainer

    def run(steps, ckpt_dir, restore):
        cfg, ctx, step_fn, state, loader = build(
            "tinyllama-1.1b", True, batch=4, seq=32, steps=6, seed=3)
        tr = Trainer(step_fn, state, loader,
                     LoopConfig(total_steps=steps, ckpt_every=3,
                                ckpt_dir=ckpt_dir, log_every=1))
        if restore:
            assert tr.maybe_restore()
        out = tr.run()
        loader.stop()
        return out, tr.state

    full, state_full = run(6, str(tmp_path / "a"), False)
    _half, _ = run(3, str(tmp_path / "b"), False)
    resumed, state_resumed = run(6, str(tmp_path / "b"), True)
    assert abs(full["final_loss"] - resumed["final_loss"]) < 1e-4
    for a, b in zip(jax.tree.leaves(state_full.params),
                    jax.tree.leaves(state_resumed.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-5)


def test_compressed_psum_error_feedback():
    """Over repeated steps on a constant gradient, error feedback makes
    the compressed reduction converge to the true mean."""
    mesh = jax.make_mesh((1,), ("pod",))
    g_true = {"w": jax.random.normal(jax.random.PRNGKey(0), (2048,))}
    err = init_error_state(g_true, block=256, dtype=jnp.float32)

    import functools
    from jax.sharding import PartitionSpec as P


    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(P(), P()),
                       out_specs=(P(), P()), check_vma=False)
    def step(g, e):
        return compressed_psum_tree(g, e, axis="pod", k_per_block=32,
                                    block=256)

    total = jax.tree.map(jnp.zeros_like, g_true)
    err_now = err
    for _ in range(8):
        synced, err_now = step(g_true, err_now)
        total = jax.tree.map(jnp.add, total, synced)
    # mean of synced over steps ≈ g_true (error feedback catches up)
    approx = total["w"] / 8
    corr = float(jnp.corrcoef(approx, g_true["w"])[0, 1])
    assert corr > 0.95, corr


def test_elastic_plan_rescale():
    from repro.distributed.elastic import ElasticPlan
    p = ElasticPlan.rescale(microbatches=4, global_batch=256,
                            old_pods=2, new_pods=1)
    assert p.microbatches == 8 and p.global_batch == 256


def test_compressed_train_step_functional():
    """End-to-end compressed cross-pod step: loss descends, error state
    evolves, per-pod replica layout round-trips."""
    import jax.numpy as jnp
    from repro.launch import specs as lspecs
    from repro.training.step import (make_compressed_train_step,
                                     replicate_state_per_pod)

    cfg = configs.get_reduced("tinyllama-1.1b")
    mesh = jax.make_mesh((1, 1), ("pod", "data"))
    opt = AdamW(constant_schedule(1e-3))
    step = make_compressed_train_step(cfg, opt, mesh, microbatches=2,
                                      block=256, k_per_block=32)
    run = configs.RunOverrides()
    state0 = lspecs.init_train_state(cfg, None, run, opt,
                                     jax.random.PRNGKey(0))
    state = replicate_state_per_pod(state0, 1)
    err = replicate_state_per_pod(
        init_error_state(state0.params, block=256), 1)
    loader = DataLoader(cfg, ShapeCell("t", "train", 64, 4), 2, seed=0)
    losses = []
    for i in range(5):
        state, err, m = step(state, loader.make_batch(i), err)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]
    assert float(jnp.abs(jax.tree.leaves(err)[0]).max()) > 0  # EF active
