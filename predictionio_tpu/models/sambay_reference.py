"""The plain reference of :mod:`predictionio_tpu.models.sambay`: one
user's WHOLE history through every layer at every position in
straightforward ``jax.numpy`` and float32, with no cache, no batching, no
kernel and no decoder split (the cross-decoder runs on every event; the
served path runs it on a turn's last alone, which is the same function).
The scan is the recurrence itself, an event at a time; the attention
masks are plain masks over the n x n score matrix.  The tests hold the
served path (prefill, then turns through all three kinds of state) to
it; training differentiates it at tier-1 sizes.

The caller sets ``jax.default_matmul_precision("highest")`` where the
backend's float32 products would otherwise run in fewer bits.  The
equations are at the head of :mod:`predictionio_tpu.models.sambay`.

For the negative controls: ``zero_lambda`` (``lambda = 0``: the second
softmax of every pair left out), ``window`` (another reach than the
configuration's), ``state_resets`` (a bool an event: the scan's state is
zeroed before that event, as if every turn began from nothing).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["forward", "mamba_mixer", "diff_attention", "layer_norm", "mlp",
           "lambda_init"]


def layer_norm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def mamba_mixer(cfg, p: Dict[str, Any], u: jax.Array,
                state_resets: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """(the mixer's output [S, d], the scan's output ``y`` [S, E] before
    the gate: the MEMORY where this is the memory layer)."""
    s, e, n, r = u.shape[0], cfg.d_inner, cfg.d_state, cfg.dt_rank
    xz = u @ p["w_in"]
    x, z = xz[:, :e], xz[:, e:]
    w = cfg.d_conv
    xpad = jnp.pad(x, ((w - 1, 0), (0, 0)))
    conv = sum(p["conv_w"][j] * xpad[j:j + s] for j in range(w))
    xc = jax.nn.silu(conv + p["conv_b"])
    proj = xc @ p["w_x"]
    delta = jax.nn.softplus(proj[:, :r] @ p["w_dt"] + p["dt_b"])
    a = -jnp.exp(p["a_log"])                               # [N, E]
    resets = jnp.zeros(s, bool) if state_resets is None else state_resets

    def event(h, row):
        xt, dt, b, c, reset = row
        h = jnp.where(reset, 0.0, h)
        h = jnp.exp(dt[None, :] * a) * h + (dt * xt)[None, :] * b[:, None]
        return h, jnp.sum(h * c[:, None], axis=0) + p["d_skip"] * xt

    _, y = jax.lax.scan(event, jnp.zeros((n, e), jnp.float32),
                        (xc, delta, proj[:, r:r + n], proj[:, r + n:],
                         resets))
    return (y * jax.nn.silu(z)) @ p["w_out"], y


def diff_attention(cfg, p: Dict[str, Any], layer: int, q: jax.Array,
                   k: jax.Array, v: jax.Array, mask: jax.Array,
                   zero_lambda: bool = False) -> jax.Array:
    """``q`` [S, H, hd], ``k``, ``v`` [S, KV, hd], ``mask`` [S, S] bool
    -> the heads' outputs [S, H * hd], before ``W_o``."""
    s, heads, hd = q.shape
    pairs = k.shape[1] // 2
    per = heads // 2 // pairs          # query pairs a kv pair
    q1 = q[:, 0::2].reshape(s, pairs, per, hd)
    q2 = q[:, 1::2].reshape(s, pairs, per, hd)
    k1, k2 = k[:, 0::2], k[:, 1::2]
    vv = v.reshape(s, pairs, 2 * hd)

    def softmax(qq, kk):
        sc = jnp.einsum("tgrd,sgd->grts", qq, kk) / math.sqrt(hd)
        return jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)

    li = lambda_init(layer)
    lam = 0.0 if zero_lambda else (
        jnp.exp(jnp.dot(p["lam"][0], p["lam"][1]))
        - jnp.exp(jnp.dot(p["lam"][2], p["lam"][3])) + li)
    w = softmax(q1, k1) - lam * softmax(q2, k2)
    o = jnp.einsum("grts,sge->tgre", w, vv)
    o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True)
                     + cfg.layer_norm_eps) * p["sub_g"] * (1.0 - li)
    return o.reshape(s, heads * hd)


def mlp(p: Dict[str, Any], u: jax.Array) -> jax.Array:
    f = p["w2"].shape[-2]
    h = u @ p["w13"]
    return (jax.nn.silu(h[..., :f]) * h[..., f:]) @ p["w2"]


def forward(params: Dict[str, Any], cfg, tokens: jax.Array, *,
            zero_lambda: bool = False, window: Optional[int] = None,
            state_resets: Optional[jax.Array] = None) -> jax.Array:
    """Logits [S, V] after each of the user's ``tokens`` [S]."""
    f32 = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                 params)
    eps = cfg.layer_norm_eps
    s = tokens.shape[0]
    heads, kv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
    pos = jnp.arange(s)
    causal = pos[None, :] <= pos[:, None]
    reach = cfg.sliding_window if window is None else window
    near = causal & (pos[:, None] - pos[None, :] < reach)
    x = f32["embed"][tokens]
    memory = shared = None
    for layer, (kind, p) in enumerate(zip(cfg.kinds, f32["layers"])):
        u = layer_norm(x, p["norm1_g"], p["norm1_b"], eps)
        if kind == "mamba":
            out, y = mamba_mixer(cfg, p, u, state_resets)
            if layer == cfg.memory_layer:
                memory = y
        elif kind == "gmu":
            out = (jax.nn.silu(u @ p["w_in"]) * memory) @ p["w_out"]
        else:
            if kind == "cross":
                q = (u @ p["w_q"] + p["b_q"]).reshape(s, heads, hd)
                k, v = shared
            else:
                qkv = u @ p["w_qkv"] + p["b_qkv"]
                q = qkv[:, :heads * hd].reshape(s, heads, hd)
                k = qkv[:, heads * hd:(heads + kv) * hd].reshape(s, kv, hd)
                v = qkv[:, (heads + kv) * hd:].reshape(s, kv, hd)
                if kind == "full":
                    shared = (k, v)
            o = diff_attention(cfg, p, layer, q, k, v,
                               near if kind == "window" else causal,
                               zero_lambda)
            out = o @ p["w_o"] + p["b_o"]
        x = x + out
        x = x + mlp(p, layer_norm(x, p["norm2_g"], p["norm2_b"], eps))
    h = layer_norm(x, f32["final_g"], f32["final_b"], eps)
    return h @ f32["embed"].T
