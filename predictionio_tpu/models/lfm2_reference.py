"""The plain reference of :mod:`predictionio_tpu.models.lfm2`: one
user's WHOLE history through every layer in straightforward
``jax.numpy`` and float32, with no cache, no batching and no kernel.
The tests hold the served path (prefill, then turns through both kinds
of state) to it; training differentiates it at tier-1 sizes.

The caller sets ``jax.default_matmul_precision("highest")`` where the
backend's float32 products would otherwise run in fewer bits.

Equations (``d`` hidden size, ``RMS(x; g) = x / sqrt(mean(x^2) + eps) *
g``): every layer ``h = x + Op(RMS(x; g_op))``, ``y = h + FF(RMS(h;
g_ffn))``; after the last one more ``RMS``, then ``logits = h E^T``
(the embedding, tied).
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from predictionio_tpu.models.lfm2 import ROUTER_EPS, LFM2Config

__all__ = ["forward", "conv_mixer", "attention_mixer", "dense_mlp",
           "expert_mlp", "router"]


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def conv_mixer(cfg: LFM2Config, p: Dict[str, Any], u: jax.Array
               ) -> jax.Array:
    """``[B, C, X] = split3(u W_in)``; ``z = B * X``; ``c_t = sum_j w_j *
    z_{t-(L-1)+j}`` (``z`` before the first event is 0); ``(C * c)
    W_out``."""
    d, taps = cfg.hidden_size, cfg.conv_L_cache
    bcx = u @ p["w_in"]
    b, c_gate, x = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    z = b * x
    zp = jnp.concatenate([jnp.zeros((taps - 1, d), z.dtype), z])
    c = sum(p["conv_w"][j] * zp[j:j + len(z)] for j in range(taps))
    return (c_gate * c) @ p["w_out"]


def _rope(x, theta):
    s, _, hd = x.shape
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def attention_mixer(cfg: LFM2Config, p: Dict[str, Any], u: jax.Array
                    ) -> jax.Array:
    """``q, k, v`` = column blocks of ``u W_qkv``; RMS over each head of
    ``q`` and of ``k``; rotate-half rotary at the event's index; causal
    ``softmax(q k^T / sqrt(hd)) v``, ``heads / kv heads`` query heads to
    a kv head; ``W_o``."""
    s, d, hd = u.shape[0], cfg.hidden_size, cfg.head_dim
    h, kvh = cfg.num_attention_heads, cfg.num_key_value_heads
    qkv = u @ p["w_qkv"]
    q = qkv[:, :d].reshape(s, h, hd)
    k = qkv[:, d:d + kvh * hd].reshape(s, kvh, hd)
    v = qkv[:, d + kvh * hd:].reshape(s, kvh, hd)
    q = _rope(_rms(q, p["q_norm"], cfg.norm_eps), cfg.rope_theta)
    k = _rope(_rms(k, p["k_norm"], cfg.norm_eps), cfg.rope_theta)
    k = jnp.repeat(k, h // kvh, axis=1)
    v = jnp.repeat(v, h // kvh, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    w = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("hts,shd->thd", w, v).reshape(s, d) @ p["w_o"]


def dense_mlp(p: Dict[str, Any], u: jax.Array) -> jax.Array:
    f = p["w2"].shape[-2]
    h = u @ p["w13"]
    return (jax.nn.silu(h[..., :f]) * h[..., f:]) @ p["w2"]


def router(cfg: LFM2Config, p: Dict[str, Any], u: jax.Array) -> jax.Array:
    """[S, E] weight of each expert for each token, zero where the expert
    is not among the token's top ``num_experts_per_tok`` of ``s + b``."""
    s = jax.nn.sigmoid(u @ p["w_g"])
    pick = s + p["b"] if cfg.use_expert_bias else s
    _, ids = jax.lax.top_k(pick, cfg.num_experts_per_tok)
    chosen = jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], ids].set(1.0)
    w = s * chosen
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, -1, keepdims=True) + ROUTER_EPS)
    return w * cfg.routed_scaling_factor


def expert_mlp(cfg: LFM2Config, p: Dict[str, Any], u: jax.Array
               ) -> jax.Array:
    """``sum_e weight_e(t) * FF_e(u_t)``: every expert on every token,
    weighed by the router (zero for the unpicked)."""
    f = cfg.moe_intermediate_size
    h = jnp.einsum("sd,edf->esf", u, p["w13"])
    y = jnp.einsum("esf,efd->esd", jax.nn.silu(h[..., :f]) * h[..., f:],
                   p["w2"])
    return jnp.einsum("se,esd->sd", router(cfg, p, u), y)


def forward(params: Dict[str, Any], cfg: LFM2Config, tokens: jax.Array
            ) -> jax.Array:
    """Logits [S, V] after each of the user's ``tokens`` [S]."""
    f32 = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                 params)
    x = f32["embed"][tokens]
    for layer, p in enumerate(f32["layers"]):
        u = _rms(x, p["op_norm"], cfg.norm_eps)
        mixer = conv_mixer if cfg.layer_types[layer] == "conv" \
            else attention_mixer
        x = x + mixer(cfg, p, u)
        u = _rms(x, p["ffn_norm"], cfg.norm_eps)
        x = x + (dense_mlp(p, u) if cfg.dense_ff[layer]
                 else expert_mlp(cfg, p, u))
    return _rms(x, f32["final_norm"], cfg.norm_eps) @ f32["embed"].T
