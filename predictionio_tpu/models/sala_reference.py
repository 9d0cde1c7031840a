"""The plain reference of :mod:`predictionio_tpu.models.sala`: one
user's WHOLE history through every layer in straightforward
``jax.numpy`` and float32, with no cache, no batching and no kernel; the
block selection is a plain mask over the n x n score matrix.  The tests
hold the served path (prefill, then turns through all three kinds of
state) to it; training differentiates it at tier-1 sizes.

The caller sets ``jax.default_matmul_precision("highest")`` where the
backend's float32 products would otherwise run in fewer bits.  The
equations are at the head of :mod:`predictionio_tpu.models.sala`.

``forced_only`` and ``no_decay`` exist for the negative controls: the
selection left out (a query past ``dense_len`` reads its forced blocks
alone) and the decay left out (``lambda = 1``).
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

__all__ = ["forward", "lightning_mixer", "sparse_mixer", "selection_mask",
           "decay_rates"]


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    s, _, hd = x.shape
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def decay_rates(cfg, layer: int) -> jax.Array:
    """``-log(lambda_h)`` of the heads of PUBLISHED layer ``layer``:
    ``s_h * (1 - l / (L - 1) + 1e-5)``, ``s_h = 2^(-8 (h + 1) / H)``."""
    h = cfg.lightning_nh
    slope = 2.0 ** (-8.0 * (jnp.arange(h, dtype=jnp.float32) + 1.0) / h)
    return slope * (1.0 - layer / (cfg.published_layers - 1) + 1e-5)


def lightning_mixer(cfg, p: Dict[str, Any], u: jax.Array, layer: int,
                    no_decay: bool = False) -> jax.Array:
    """``S_t = lambda S_{t-1} + k_t^T v_t``, ``o_t = q_t S_t / sqrt(hd)``,
    written as the masked product it unrolls to: ``o_t = sum_{j<=t}
    lambda^(t-j) (q_t . k_j) v_j``."""
    s, h, hd = u.shape[0], cfg.lightning_nh, cfg.lightning_head_dim
    qkv = (u @ p["w_qkv"]).reshape(s, 3, h, hd)
    q = _rope(_rms(qkv[:, 0], p["q_norm"], cfg.rms_norm_eps), cfg.rope_theta)
    k = _rope(_rms(qkv[:, 1], p["k_norm"], cfg.rms_norm_eps), cfg.rope_theta)
    v = qkv[:, 2]
    rate = jnp.zeros(h) if no_decay else decay_rates(cfg, layer)
    t = jnp.arange(s)
    lag = (t[:, None] - t[None, :]).astype(jnp.float32)
    decay = jnp.where(lag >= 0, jnp.exp(-rate[:, None, None]
                                        * jnp.maximum(lag, 0.0)), 0.0)
    w = jnp.einsum("thd,shd->hts", q, k) * decay / math.sqrt(hd)
    o = jnp.einsum("hts,shd->thd", w, v).reshape(s, h * hd)
    gate = jax.nn.sigmoid(u @ p["w_z"])
    return (gate * _rms(o, p["o_norm"], cfg.rms_norm_eps)) @ p["w_o"]


def selection_mask(cfg, q: jax.Array, k: jax.Array,
                   forced_only: bool = False) -> jax.Array:
    """[groups, n, n] bool: the events query ``t`` of a group attends to
    (causality included).  ``q`` [n, H, hd], ``k`` [n, KV, hd], normed."""
    n, heads, hd = q.shape
    kv = k.shape[1]
    ks, st, bs = cfg.kernel_size, cfg.kernel_stride, cfg.block_size
    per = bs // st
    pos = jnp.arange(n)
    causal = pos[None, :] <= pos[:, None]
    dense = (pos + 1 <= cfg.dense_len)[:, None]
    nb = -(-n // bs)
    bq = pos // bs
    b = jnp.arange(nb)
    forced = ((b[None, :] < cfg.init_blocks)
              | ((b[None, :] <= bq[:, None])
                 & (b[None, :] > bq[:, None] - cfg.window_size // bs)))
    nj = max((n - ks) // st + 1, 0)
    if nj == 0 or forced_only:
        picked = jnp.broadcast_to(forced[None], (kv, n, nb))
    else:
        win = (jnp.arange(nj) * st)[:, None] + jnp.arange(ks)[None, :]
        pooled = jnp.mean(k[win], axis=1)                  # [nj, KV, hd]
        ends = jnp.arange(nj) * st + ks - 1
        seen = ends[None, :] <= pos[:, None]               # [n, nj]
        qg = q.reshape(n, kv, heads // kv, hd)
        s = jnp.einsum("tghd,jgd->ghtj", qg, pooled) / math.sqrt(hd)
        a = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), -1)
        a = jnp.where(seen[None, None], a, 0.0).sum(1)     # [KV, n, nj]
        # Block b: the widest of windows per*b - 1 ... per*b + per - 1.
        pad = jnp.pad(a, ((0, 0), (0, 0), (1, nb * per - nj + per)))
        idx = (b * per)[:, None] + jnp.arange(per + 1)[None, :]
        score = jnp.max(pad[:, :, idx], axis=-1)           # [KV, n, nb]
        score = jnp.where(forced[None], 1e30, score)
        score = jnp.where((b[None, :] <= bq[:, None])[None], score, -1e30)
        _, ids = jax.lax.top_k(score, min(cfg.topk, nb))
        picked = jnp.zeros((kv, n, nb), bool).at[
            jnp.arange(kv)[:, None, None], pos[None, :, None], ids].set(True)
    in_block = jnp.repeat(picked, bs, axis=2)[:, :, :n]
    return causal[None] & (dense[None] | in_block)


def sparse_mixer(cfg, p: Dict[str, Any], u: jax.Array,
                 forced_only: bool = False) -> jax.Array:
    s, h, hd = u.shape[0], cfg.num_attention_heads, cfg.head_dim
    kv = cfg.num_key_value_heads
    qkv = u @ p["w_qkv"]
    q = _rms(qkv[:, :h * hd].reshape(s, h, hd), p["q_norm"],
             cfg.rms_norm_eps)
    k = _rms(qkv[:, h * hd:(h + kv) * hd].reshape(s, kv, hd), p["k_norm"],
             cfg.rms_norm_eps)
    v = qkv[:, (h + kv) * hd:].reshape(s, kv, hd)
    mask = jax.lax.stop_gradient(selection_mask(cfg, q, k, forced_only))
    qg = q.reshape(s, kv, h // kv, hd)
    scores = jnp.einsum("tghd,sgd->ghts", qg, k) / math.sqrt(hd)
    w = jax.nn.softmax(jnp.where(mask[:, None], scores, -jnp.inf), axis=-1)
    o = jnp.einsum("ghts,sgd->tghd", w, v).reshape(s, h * hd)
    return (jax.nn.sigmoid(u @ p["w_z"]) * o) @ p["w_o"]


def mlp(p: Dict[str, Any], u: jax.Array) -> jax.Array:
    f = p["w2"].shape[-2]
    h = u @ p["w13"]
    return (jax.nn.silu(h[..., :f]) * h[..., f:]) @ p["w2"]


def forward(params: Dict[str, Any], cfg, tokens: jax.Array, *,
            forced_only: bool = False, no_decay: bool = False) -> jax.Array:
    """Logits [S, V] after each of the user's ``tokens`` [S]."""
    f32 = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                 params)
    c = cfg.residual_scale
    x = cfg.scale_emb * f32["embed"][tokens]
    for i, p in enumerate(f32["layers"]):
        u = _rms(x, p["op_norm"], cfg.rms_norm_eps)
        if cfg.mixer_types[i] == "lightning-attn":
            out = lightning_mixer(cfg, p, u, cfg.layer_index[i], no_decay)
        else:
            out = sparse_mixer(cfg, p, u, forced_only)
        x = x + c * out
        x = x + c * mlp(p, _rms(x, p["ffn_norm"], cfg.rms_norm_eps))
    h = _rms(x, f32["final_norm"], cfg.rms_norm_eps) / cfg.head_divisor
    return h @ f32["head"].T
