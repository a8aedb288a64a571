"""Plain reference of the ``node18_cifar`` configuration, in float32
``jax.numpy``: the continuous-depth transformer LM, its training step
(AdamW, cosine warm-up, global-norm clipping) and its token stream.

Each of the 18 layers is an ODE block ``z(1) = z(0) + int_0^1 f(z) dt``
whose dynamics ``f(z) = block(z) - z`` are a pre-norm transformer block
(RMSNorm, causal multi-head attention with half-split rotary positions,
RMSNorm, SwiGLU feed-forward), solved over the whole (batch, seq, d)
state with adaptive Heun-Euler and differentiated the way ACA does: the
gradient of the accepted steps' discrete map, step sizes held constant
(``harness/ref_ode.py``).

``quant="fp8"`` computes every matrix product the usual float8 way,
operands rounded to float8_e4m3 and cotangents to float8_e5m2, each
under a per-tensor scale: the control one precision below the
configuration's bfloat16.
"""

from __future__ import annotations

import functools
import zlib
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from harness import counts, ref_ode

HIGHEST = jax.lax.Precision.HIGHEST
HEUN_EULER = ref_ode.Tableau(counts.HEUN_EULER_A, counts.HEUN_EULER_B,
                             counts.HEUN_EULER_E, counts.HEUN_EULER_C, 2,
                             False)


# ------------------------------------------------------------ parameters

def param_shapes(config) -> Dict[str, tuple]:
    """Leaf path -> shape, the stacked layer dimension first."""
    m = config["model"]
    L, d, f, V = m["n_layers"], m["d_model"], m["d_ff"], m["vocab"]
    hd = m["head_dim"]
    s = "stack/u0_attn/"
    return {
        "embed": (V, d),
        s + "norm1/w": (L, d),
        s + "mixer/wq": (L, d, m["n_heads"] * hd),
        s + "mixer/wk": (L, d, m["n_kv_heads"] * hd),
        s + "mixer/wv": (L, d, m["n_kv_heads"] * hd),
        s + "mixer/wo": (L, m["n_heads"] * hd, d),
        s + "norm2/w": (L, d),
        s + "ffn/w_in": (L, d, f),
        s + "ffn/w_out": (L, f, d),
        s + "ffn/w_gate": (L, d, f),
        "final_norm/w": (d,),
        "lm_head": (d, V),
    }


def init_leaf(key, path: str, shape, config):
    """One leaf from the seed's key and the leaf's path."""
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    if path.endswith("norm1/w") or path.endswith("norm2/w") \
            or path == "final_norm/w":
        return jnp.ones(shape, jnp.float32)
    if path in ("embed", "lm_head"):
        std = config["init"]["embed_std"]
    else:
        std = 1.0 / float(np.prod(shape[:-1])) ** 0.5
    return jax.random.normal(k, shape, jnp.float32) * std


def init_params(key, config) -> Dict[str, jnp.ndarray]:
    """The configuration's network from its fixed ``init.base_seed``,
    with every layer's heads and feed-forward units reordered by ``key``:
    an equivalent network for every seed, so that every seed asks the
    adaptive solves for the same work."""
    base = jax.random.PRNGKey(config["init"]["base_seed"])
    p = {path: init_leaf(base, path, s, config)
         for path, s in param_shapes(config).items()}
    m = config["model"]
    L, H, hd, F = m["n_layers"], m["n_heads"], m["head_dim"], m["d_ff"]
    kh, kf = jax.random.split(key)
    ph = jax.vmap(lambda k: jax.random.permutation(k, H))(
        jax.random.split(kh, L))                                # (L, H)
    pf = jax.vmap(lambda k: jax.random.permutation(k, F))(
        jax.random.split(kf, L))                                # (L, F)
    s = "stack/u0_attn/"
    for name in ("mixer/wq", "mixer/wk", "mixer/wv"):
        w = p[s + name]
        w = w.reshape(L, w.shape[1], H, hd)
        p[s + name] = jnp.take_along_axis(
            w, ph[:, None, :, None], axis=2).reshape(L, -1, H * hd)
    wo = p[s + "mixer/wo"].reshape(L, H, hd, -1)
    p[s + "mixer/wo"] = jnp.take_along_axis(
        wo, ph[:, :, None, None], axis=1).reshape(L, H * hd, -1)
    for name in ("ffn/w_in", "ffn/w_gate"):
        p[s + name] = jnp.take_along_axis(p[s + name], pf[:, None, :], 2)
    p[s + "ffn/w_out"] = jnp.take_along_axis(p[s + "ffn/w_out"],
                                             pf[:, :, None], 1)
    return p


# ----------------------------------------------------------------- tokens

def tokens(seed: int, step: int, batch: int, seq: int, vocab: int,
           zipf_a: float):
    """Zipf token rows, row r of step ``step`` drawn from
    SeedSequence([seed, step, r]) (the repo's synthetic LM stream)."""
    rows = []
    for r in range(batch):
        rng = np.random.default_rng(np.random.SeedSequence([seed, step, r]))
        rows.append(rng.zipf(zipf_a, size=seq + 1))
    toks = np.minimum(np.stack(rows) - 1, vocab - 1).astype(np.int32)
    return toks[:, :-1], toks[:, 1:]


# ------------------------------------------------------------------ model

def _fp8(x, dtype):
    """x rounded to a float8 format under a per-tensor scale."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / float(
        jnp.finfo(dtype).max)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mm_fp8(spec, a, b):
    return jnp.einsum(spec, _fp8(a, jnp.float8_e4m3fn),
                      _fp8(b, jnp.float8_e4m3fn), precision=HIGHEST)


def _mm_fp8_fwd(spec, a, b):
    aq, bq = _fp8(a, jnp.float8_e4m3fn), _fp8(b, jnp.float8_e4m3fn)
    return jnp.einsum(spec, aq, bq, precision=HIGHEST), (aq, bq)


def _mm_fp8_bwd(spec, res, g):
    # the usual float8 recipe: e4m3 operands forward, e5m2 cotangents
    _, vjp = jax.vjp(lambda x, y: jnp.einsum(spec, x, y, precision=HIGHEST),
                     *res)
    return vjp(_fp8(g, jnp.float8_e5m2))


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


def _mm(spec, a, b, quant):
    if quant == "fp8":
        return _mm_fp8(spec, a, b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    S, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def block_branch(p, z, m, quant):
    """f(z) = block(z) - z for one layer's parameters ``p``."""
    B, S, d = z.shape
    H, hd = m["n_heads"], m["head_dim"]
    h = _rms(z, p["norm1/w"], m["norm_eps"])
    q = _rope(_mm("bsd,df->bsf", h, p["mixer/wq"], quant)
              .reshape(B, S, H, hd), m["rope_theta"])
    k = _rope(_mm("bsd,df->bsf", h, p["mixer/wk"], quant)
              .reshape(B, S, H, hd), m["rope_theta"])
    v = _mm("bsd,df->bsf", h, p["mixer/wv"], quant).reshape(B, S, H, hd)
    s = _mm("bqhd,bkhd->bhqk", q, k, quant) / hd ** 0.5
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal, s, -1e30)
    a = _mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v, quant)
    attn = _mm("bsf,fd->bsd", a.reshape(B, S, H * hd), p["mixer/wo"], quant)
    y = z + attn
    h2 = _rms(y, p["norm2/w"], m["norm_eps"])
    g = jax.nn.silu(_mm("bsd,df->bsf", h2, p["ffn/w_gate"], quant))
    u = _mm("bsd,df->bsf", h2, p["ffn/w_in"], quant)
    return attn + _mm("bsf,fd->bsd", g * u, p["ffn/w_out"], quant)


def _layer_field(p, shape, m, quant):
    def f(t, z):
        return block_branch(p, z.reshape(shape), m, quant).reshape(1, -1)
    return f


def _layers(params):
    return {k[len("stack/u0_attn/"):]: v for k, v in params.items()
            if k.startswith("stack/")}


def forward_grids(params, toks, config, quant):
    """Accepted step sizes (L, max_steps) of every layer's solve."""
    m, node = config["model"], config["node"]
    x = params["embed"][toks]

    def layer(x, p):
        f = _layer_field(p, x.shape, m, quant)
        sol = ref_ode.solve(f, x.reshape(1, -1), node["t0"], node["t1"],
                            HEUN_EULER, node["rtol"], node["atol"],
                            node["max_steps"])
        return sol.z1.reshape(x.shape), (sol.hs[0], sol.ok[0])

    _, (hs, ok) = jax.lax.scan(layer, x, _layers(params))
    return hs, ok


def loss_on_grid(params, toks, labels, hs, config, quant):
    m, node = config["model"], config["node"]
    x = params["embed"][toks]

    @jax.checkpoint
    def layer(x, inp):
        p, h = inp
        f = _layer_field(p, x.shape, m, quant)
        z = ref_ode.replay(f, x.reshape(1, -1), node["t0"], h[None, :],
                           HEUN_EULER)
        return z.reshape(x.shape), None

    x, _ = jax.lax.scan(layer, x, (_layers(params), hs))
    x = _rms(x, params["final_norm/w"], m["norm_eps"])
    logits = _mm("bsd,dv->bsv", x, params["lm_head"], quant)
    lse = jax.nn.logsumexp(logits, -1)
    ll = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - ll)


# --------------------------------------------------------------- training

def lr_at(step: int, o) -> float:
    """Linear warm-up to the peak, then cosine to final_frac of it."""
    import math
    if step < o["warmup_steps"]:
        return o["peak_lr"] * step / max(o["warmup_steps"], 1)
    t = min(max((step - o["warmup_steps"])
                / max(o["total_steps"] - o["warmup_steps"], 1), 0.0), 1.0)
    return o["peak_lr"] * (o["final_frac"] + (1 - o["final_frac"]) * 0.5
                           * (1 + math.cos(math.pi * t)))


def train(params, batches: List, config, quant=None):
    """Steps of AdamW on ``batches`` [(tokens, labels)]; returns the
    losses, the first step's clipped gradient norm per leaf, and the
    final parameters."""
    o = config["optimizer"]
    grids = jax.jit(lambda p, t: forward_grids(p, t, config, quant))
    vg = jax.jit(jax.value_and_grad(
        lambda p, t, l, h: loss_on_grid(p, t, l, h, config, quant)))

    @jax.jit
    def adam(params, grads, mu, nu, step, lr):
        gn = jnp.sqrt(sum(jnp.sum(g * g) for g in grads.values()))
        scale = jnp.minimum(1.0, o["clip_norm"] / jnp.maximum(gn, 1e-12))
        grads = {k: g * scale for k, g in grads.items()}
        c1 = 1.0 - o["b1"] ** step
        c2 = 1.0 - o["b2"] ** step
        new_p, new_mu, new_nu = {}, {}, {}
        for k, p in params.items():
            mu_k = o["b1"] * mu[k] + (1 - o["b1"]) * grads[k]
            nu_k = o["b2"] * nu[k] + (1 - o["b2"]) * grads[k] ** 2
            u = -lr * ((mu_k / c1) / (jnp.sqrt(nu_k / c2) + o["eps"]))
            if p.ndim >= 2:
                u = u - lr * o["weight_decay"] * p
            new_p[k], new_mu[k], new_nu[k] = p + u, mu_k, nu_k
        norms = {k: jnp.sqrt(jnp.sum(g * g)) for k, g in grads.items()}
        return new_p, new_mu, new_nu, norms

    mu = {k: jnp.zeros_like(v) for k, v in params.items()}
    nu = {k: jnp.zeros_like(v) for k, v in params.items()}
    losses, grad_norms, unfinished = [], None, 0
    for i, (toks, labels) in enumerate(batches):
        toks, labels = jnp.asarray(toks), jnp.asarray(labels)
        hs, ok = grids(params, toks)
        n = max(int(np.max(np.sum(np.asarray(hs) > 0, axis=1))), 1)
        unfinished += int(np.sum(~np.asarray(ok)))
        loss, grads = vg(params, toks, labels, hs[:, :n])
        params, mu, nu, norms = adam(params, grads, mu, nu,
                                     float(i + 1), lr_at(i + 1, o))
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = {k: float(v) for k, v in norms.items()}
    return losses, grad_norms, params, unfinished
