"""Train loop: loss goes down, checkpoint/restart is exact, compression
error feedback is sound, straggler hook fires."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import CheckpointManager, save_checkpoint
from repro.data import TokenPipeline
from repro.models import ModelConfig, RunConfig, build_model
from repro.optim import adamw, cosine_warmup, sgd, step_decay
from repro.optim.grad_utils import (clip_by_global_norm, global_norm,
                                    init_compression_state,
                                    int8_compress_decompress,
                                    topk_sparsify)
from repro.train import TrainLoop, TrainLoopConfig, make_train_state

CFG = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                  vocab=256, n_heads=4, n_kv_heads=2, d_ff=128)


def _loop(tmpdir, **kw):
    m = build_model(CFG, RunConfig(compute_dtype=jnp.float32))
    opt = adamw(cosine_warmup(3e-3, 5, 200), weight_decay=0.01)
    lcfg = TrainLoopConfig(ckpt_dir=str(tmpdir) if tmpdir else None,
                           ckpt_every=5, log_every=1, **kw)
    state = make_train_state(m, opt, jax.random.PRNGKey(0))
    return m, opt, lcfg, TrainLoop(m, opt, lcfg, state)


def test_loss_decreases(tmp_path):
    pipe = TokenPipeline(vocab=256, seq_len=32, global_batch=8)
    _, _, _, loop = _loop(None)
    losses = []
    loop.run(lambda s: pipe.batch(0), 25,        # overfit one batch
             log_cb=lambda s, mt: losses.append(mt["loss"]))
    assert losses[-1] < losses[0] * 0.7, losses[:3] + losses[-3:]


def test_checkpoint_restart_exact(tmp_path):
    pipe = TokenPipeline(vocab=256, seq_len=32, global_batch=8)
    m, opt, lcfg, loop = _loop(tmp_path)
    loop.run(lambda s: pipe.batch(s), 10)
    params_10 = jax.tree.leaves(loop.state.params)

    # a fresh loop restores step 10 exactly and continues
    state2 = make_train_state(m, opt, jax.random.PRNGKey(42))
    loop2 = TrainLoop(m, opt, lcfg, state2)
    assert loop2.step == 10
    for a, b in zip(params_10, jax.tree.leaves(loop2.state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # deterministic data: running 10->12 equals an uninterrupted run
    loop2.run(lambda s: pipe.batch(s), 12)
    _, _, _, loop3 = _loop(None)
    loop3.run(lambda s: pipe.batch(s), 12)
    for a, b in zip(jax.tree.leaves(loop2.state.params),
                    jax.tree.leaves(loop3.state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_ckpt_atomicity_and_fallback(tmp_path):
    tree = {"w": jnp.arange(6.0).reshape(2, 3), "b": jnp.zeros((3,))}
    mgr = CheckpointManager(str(tmp_path), keep=2)
    mgr.save(1, tree)
    mgr.save(2, jax.tree.map(lambda x: x + 1, tree))
    # corrupt the newest manifest -> restore falls back to step 1
    os.remove(os.path.join(str(tmp_path), "step_0000000002",
                           "manifest.json"))
    step, restored = mgr.restore(tree)
    assert step == 1
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(tree["w"]))


def test_ckpt_keep_k_gc(tmp_path):
    tree = {"x": jnp.ones((2,))}
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, tree)
    names = sorted(os.listdir(str(tmp_path)))
    assert names == ["step_0000000003", "step_0000000004"]


def test_ckpt_shape_mismatch_rejected(tmp_path):
    save_checkpoint(str(tmp_path), 1, {"x": jnp.ones((2,))})
    mgr = CheckpointManager(str(tmp_path))
    assert mgr.restore({"x": jnp.ones((3,))}) is None


def test_microbatch_accumulation_matches_full_batch():
    pipe = TokenPipeline(vocab=256, seq_len=16, global_batch=8)
    m = build_model(CFG, RunConfig(compute_dtype=jnp.float32))
    opt = sgd(step_decay(0.1, [1000]), momentum=0.0)
    from repro.optim.grad_utils import CompressionState
    from repro.train.loop import build_train_step
    batch = pipe.batch(0)
    s1 = build_train_step(m, opt, TrainLoopConfig(microbatches=1,
                                                  clip_norm=1e9))
    s4 = build_train_step(m, opt, TrainLoopConfig(microbatches=4,
                                                  clip_norm=1e9))
    st = make_train_state(m, opt, jax.random.PRNGKey(0))
    r1, _, _ = s1(st, batch, CompressionState(error=()))
    st = make_train_state(m, opt, jax.random.PRNGKey(0))
    r4, _, _ = s4(st, batch, CompressionState(error=()))
    for a, b in zip(jax.tree.leaves(r1.params),
                    jax.tree.leaves(r4.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


def test_clip_by_global_norm():
    g = {"a": jnp.ones((4,)) * 3.0, "b": jnp.ones((2, 2)) * 4.0}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert abs(float(global_norm(clipped)) - 1.0) < 1e-5
    assert float(norm) > 1.0
    # below the threshold: unchanged
    clipped2, _ = clip_by_global_norm(g, 1e9)
    np.testing.assert_allclose(np.asarray(clipped2["a"]),
                               np.asarray(g["a"]))


def test_int8_compression_error_feedback():
    """Error feedback makes repeated compression of a constant gradient
    unbiased: the mean dequantized value converges to the truth."""
    g = {"w": jnp.linspace(-1.0, 1.0, 101) * 1e-3}
    state = init_compression_state(g)
    total = jnp.zeros_like(g["w"])
    n = 50
    for _ in range(n):
        out, state = int8_compress_decompress(g, state)
        total = total + out["w"]
    np.testing.assert_allclose(np.asarray(total / n),
                               np.asarray(g["w"]), rtol=0.02, atol=2e-7)


def test_topk_sparsity_and_feedback():
    g = {"w": jnp.arange(1.0, 101.0)}
    out, state = topk_sparsify(g, 0.1)
    nz = int(jnp.sum(out["w"] != 0))
    assert nz == 10
    # the residual holds everything that was dropped
    np.testing.assert_allclose(
        np.asarray(out["w"] + state.error["w"]), np.asarray(g["w"]),
        rtol=1e-6)


def test_straggler_hook_fires():
    pipe = TokenPipeline(vocab=256, seq_len=16, global_batch=4)
    hits = []
    m = build_model(CFG, RunConfig(compute_dtype=jnp.float32))
    opt = adamw(cosine_warmup(1e-3, 5, 100))
    lcfg = TrainLoopConfig(straggler_factor=3.0)
    state = make_train_state(m, opt, jax.random.PRNGKey(0))
    # injected clock, read at each phase boundary of a step: every read
    # once step 2's batch is made advances it 31 fake-seconds, the
    # others 1 (step 2 is a straggler)
    now, cur = [0.0], [0]

    def fake_clock():
        now[0] += 31.0 if cur[0] == 2 else 1.0
        return now[0]

    def batch(s):
        cur[0] = s
        return pipe.batch(s)

    loop = TrainLoop(m, opt, lcfg, state, clock=fake_clock,
                     straggler_cb=lambda s, ratio: hits.append((s, ratio)))
    loop.run(batch, 5)
    assert hits, "straggler callback never fired"
    assert max(r for _, r in hits) > 5
