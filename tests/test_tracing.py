"""What the program records about its own work: the solver's named
scopes in compiled HLO, the NODE blocks' solve counters surfaced by the
train step, and ``TrainLoop``'s host phase spans."""

import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.node18_cifar import NODE_TRAIN, SMOKE
from repro.core import odeint, odeint_final
from repro.core.controller import ControllerConfig
from repro.core.integrate import CKPT_WRITE_SCOPE
from repro.core.odeint_aca import ACA_BACKWARD_SCOPE
from repro.core.stepper import FIELD_SCOPE
from repro.data import TokenPipeline
from repro.models import ModelConfig, RunConfig, build_model
from repro.models.lm import _embed
from repro.models.transformer import block_apply
from repro.optim import adamw, cosine_warmup
from repro.train import TrainLoop, TrainLoopConfig, make_train_state

SCOPES = (FIELD_SCOPE, CKPT_WRITE_SCOPE, ACA_BACKWARD_SCOPE)
PHASES = ("batch", "dispatch", "wait")


def _scopes_in(hlo_text):
    """Solver scopes that are a component of some op_name in the text
    (bare or inside a transformation such as ``transpose(jvp(...))``)."""
    names = re.findall(r'op_name="([^"]*)"', hlo_text)
    return {s for s in SCOPES for n in names
            if re.search(r"(?:^|[/(])" + s + r"(?:[/)]|$)", n)}


@pytest.fixture(scope="module")
def node_loop():
    """The node18 smoke configuration under NODE_TRAIN (HeunEuler, ACA,
    segmented checkpoints, Pallas kernels in interpret mode), in f32."""
    model = build_model(SMOKE, RunConfig(compute_dtype=jnp.float32,
                                         node=NODE_TRAIN, remat="none"))
    opt = adamw(cosine_warmup(3e-4, 20, 300), weight_decay=0.1)
    state = make_train_state(model, opt, jax.random.PRNGKey(0))
    pipe = TokenPipeline(vocab=SMOKE.vocab, seq_len=16, global_batch=2,
                         seed=0)
    loop = TrainLoop(model, opt, TrainLoopConfig(log_every=1), state)
    return model, loop, pipe


def test_train_step_hlo_carries_the_solver_scopes(node_loop):
    _, loop, pipe = node_loop
    text = loop._step_fn.lower(loop.state, pipe.batch(0),
                               loop.comp_state).compile().as_text()
    assert _scopes_in(text) == set(SCOPES)


@pytest.mark.parametrize("segments", [None, 2])
@pytest.mark.parametrize("batch_axis", [0, None])
def test_aca_solve_hlo_carries_the_solver_scopes(batch_axis, segments):
    def loss(z0, w):
        ys, _ = odeint(lambda t, z, w: jnp.tanh(z @ w), z0,
                       jnp.asarray([0.0, 1.0]), (w,), solver="heun_euler",
                       grad_method="aca", rtol=1e-3, atol=1e-3,
                       max_steps=16, use_pallas=True,
                       batch_axis=batch_axis, checkpoint_segments=segments)
        return jnp.sum(ys[-1] ** 2)

    z0, w = jnp.full((4, 8), 0.1), 0.5 * jnp.eye(8)
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        z0, w).compile().as_text()
    assert _scopes_in(text) == set(SCOPES)


def test_node_stats_match_each_block_solved_alone(node_loop):
    model, loop, pipe = node_loop
    cfg, rcfg, node = model.cfg, model.rcfg, NODE_TRAIN
    params = jax.tree.map(jnp.copy, loop.state.params)   # the step donates
    batch = pipe.batch(loop.step)
    logged = []
    metrics = loop.run(pipe.batch, loop.step + 1,
                       log_cb=lambda s, m: logged.append(m))
    stats = jax.device_get(metrics["node_stats"])
    assert stats.nfe.shape == (cfg.n_layers,)

    def block(t, z, p):
        return block_apply(p, z, cfg, rcfg, "attn")[0] - z

    solve = jax.jit(lambda x, p: odeint_final(
        block, x, node.t0, node.t1, (p,), solver=node.solver,
        grad_method=node.grad_method, rtol=node.rtol, atol=node.atol,
        max_steps=node.max_steps, use_pallas=node.use_pallas,
        checkpoint_segments=node.checkpoint_segments))
    x = _embed(params, batch, cfg, rcfg)
    for i in range(cfg.n_layers):
        p = jax.tree.map(lambda v: v[i], params["stack"]["u0_attn"])
        x, alone = solve(x, p)
        for field in ("n_steps", "n_trials", "nfe", "status"):
            assert int(getattr(stats, field)[i]) \
                == int(getattr(alone, field)), (i, field)

    # log_cb takes the per-layer counters as lists, scalars as floats
    m = logged[-1]
    assert isinstance(m["loss"], float)
    assert m["node_stats"].nfe == stats.nfe.tolist()
    budget = 2 * node.max_steps * ControllerConfig().max_trials
    assert all(1 <= n <= budget for n in m["node_stats"].nfe)


def _dense_loop(tmp_path=None, clock=None):
    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
                      vocab=128, n_heads=2, n_kv_heads=2, d_ff=64)
    model = build_model(cfg, RunConfig(compute_dtype=jnp.float32))
    opt = adamw(cosine_warmup(1e-3, 5, 100))
    lcfg = TrainLoopConfig(ckpt_dir=str(tmp_path) if tmp_path else None,
                           ckpt_every=1)
    kw = {} if clock is None else {"clock": clock}
    state = make_train_state(model, opt, jax.random.PRNGKey(0))
    pipe = TokenPipeline(vocab=128, seq_len=8, global_batch=2)
    return TrainLoop(model, opt, lcfg, state, **kw), pipe


def test_train_phases_are_host_spans_in_a_profile(tmp_path):
    from jax.profiler import ProfileData

    loop, pipe = _dense_loop(tmp_path / "ckpt")
    loop.run(pipe.batch, 1)                       # compile outside
    with jax.profiler.trace(str(tmp_path / "prof")):
        loop.run(pipe.batch, 3)
    path, = glob.glob(str(tmp_path / "prof" / "**" / "*.xplane.pb"),
                      recursive=True)
    names = {ev.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    spans = {n for n in names if n.startswith("train/")}
    assert spans == {"train/" + p for p in PHASES + ("ckpt_save",)}


def test_last_phases_follow_an_injected_clock():
    now = [0.0]

    def advance(dt):
        now[0] += dt

    loop, pipe = _dense_loop(clock=lambda: now[0])
    step_fn = loop._step_fn

    def slow_step(*a):
        advance(2.0)
        return step_fn(*a)

    loop._step_fn = slow_step

    def batch(s):
        advance(0.25)
        return pipe.batch(s)

    loop.run(batch, 2)
    assert loop.last_phases == {"batch": 0.25, "dispatch": 2.0, "wait": 0.0}
    assert np.isclose(loop._ema_dt, 2.0)          # the straggler's step time
