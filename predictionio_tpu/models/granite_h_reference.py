"""The plain reference of :mod:`predictionio_tpu.models.granite_h`: one
user's WHOLE history through every layer at every position in
straightforward ``jax.numpy`` and float32, with no cache, no batching, no
tile and no kernel.  The Mamba-2 state is the recurrence itself, an event
at a time; the attention mask is a plain mask over the n x n score
matrix.  The tests hold the served path (prefill in chunks, then turns
through both kinds of state) to it; training differentiates it at tier-1
sizes.

**Equations** (Granite-4.0-H: ``granitemoehybrid`` with no routed
expert; ``d`` hidden size; ``RMS_n(x; g) = x / sqrt(mean_n(x^2) + eps) *
g``; the head tied to the embedding):

* Input ``x_0 = embedding_multiplier * E[item]``.
* Layer: ``h = x + residual_multiplier * Mixer(RMS_d(x))``, ``y = h +
  residual_multiplier * MLP(RMS_d(h))``, ``MLP(u) = W_out(silu(a) * b)``,
  ``[a | b] = W_in u`` (the ``shared_intermediate_size`` feed-forward:
  with ``num_local_experts`` 0 it is the block's only one).
* Mamba-2 mixer (``E = mamba_expand * d`` = ``H`` heads of ``P``; ``N =
  mamba_d_state``; one group of ``B`` and ``C`` for all heads; conv width
  ``mamba_d_conv`` with bias; no projection bias): ``[z | xBC | dt] =
  W_in u`` (``E | E + 2 N | H``); ``xBC_t = silu(sum_j w_conv[j] *
  xBC_{t-3+j} + b_conv)``, causal and depthwise; ``[x | B | C] = xBC``;
  ``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)``, a number a head;
  per head ``h`` and event ``t``: ``S_t = exp(dt_t A_h) S_{t-1} + dt_t
  x_t[h] (x) B_t`` (``P x N``), ``y_t[h] = S_t C_t + D_h x_t[h]``; ``y =
  RMS_E(y * silu(z); g)`` (the gate first, then ONE norm over all ``E``);
  ``out = W_out y``.
* Attention mixer: ``[q | k | v] = W u`` (``num_attention_heads`` /
  ``num_key_value_heads`` / the same, heads of ``head_dim``; no bias; NO
  positional encoding: ``position_embedding_type`` "nope"); query head
  ``i`` reads kv head ``i // (heads / kv heads)``; causal softmax of
  ``attention_multiplier * q k^T`` (the published 0.015625 = 1 / 64, not
  ``1 / sqrt(64)``); ``out = W_o o``.
* Output: ``logits = RMS_d(x) E^T / logits_scaling``.

Departures from the published description, each noted: none in the
arithmetic.  What the published configuration does not carry is
assumed and listed where a configuration is stated
(``benchmark/configs/granite-4.0-h-micro-l40.json``, ``assumed``): the
order of ``W_in``'s columns, gate-then-norm, no clamp on ``dt``
(``time_step_limit`` (0, inf)).

The caller sets ``jax.default_matmul_precision("highest")`` where the
backend's float32 products would otherwise run in fewer bits.

For the negative controls and the tests: ``state_resets`` (a bool an
event: the Mamba-2 state is zeroed before that event, as if every turn
began from nothing) and ``rotary`` (rotate-half rotary embedding on ``q``
and ``k``, which the published model does NOT have).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

__all__ = ["forward", "mamba2_mixer", "attention_mixer", "rms", "mlp"]


def rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def mlp(p: Dict[str, Any], u: jax.Array) -> jax.Array:
    f = p["w2"].shape[-2]
    h = u @ p["w13"]
    return (jax.nn.silu(h[..., :f]) * h[..., f:]) @ p["w2"]


def mamba2_mixer(cfg, p: Dict[str, Any], u: jax.Array,
                 state_resets: Optional[jax.Array] = None) -> jax.Array:
    """The Mamba-2 mixer's output [S, d] of ``u`` [S, d]."""
    s = u.shape[0]
    e, n = cfg.d_inner, cfg.mamba_d_state
    heads, hp = cfg.mamba_n_heads, cfg.mamba_d_head
    proj = u @ p["w_in"]
    z, xbc, dt = proj[:, :e], proj[:, e:2 * e + 2 * n], proj[:, 2 * e + 2 * n:]
    w = cfg.mamba_d_conv
    pad = jnp.pad(xbc, ((w - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(p["conv_w"][j] * pad[j:j + s] for j in range(w))
                      + p["conv_b"])
    x = xbc[:, :e].reshape(s, heads, hp)
    b, c = xbc[:, e:e + n], xbc[:, e + n:]
    dt = jax.nn.softplus(dt + p["dt_b"])                    # [S, H]
    a = -jnp.exp(p["a_log"])                                # [H]
    resets = jnp.zeros(s, bool) if state_resets is None else state_resets

    def event(state, row):
        xt, dtt, bt, ct, reset = row
        state = jnp.where(reset, 0.0, state)
        state = jnp.exp(dtt * a)[:, None, None] * state \
            + (dtt[:, None] * xt)[:, :, None] * bt[None, None, :]
        return state, state @ ct + p["d_skip"][:, None] * xt

    _, y = jax.lax.scan(event, jnp.zeros((heads, hp, n), jnp.float32),
                        (x, dt, b, c, resets))
    y = rms(y.reshape(s, e) * jax.nn.silu(z), p["gate_norm"],
            cfg.rms_norm_eps)
    return y @ p["w_out"]


def _rope(x, theta):
    """Rotate-half rotary embedding of ``x`` [S, heads, hd] (the control
    only: the published model has none)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def attention_mixer(cfg, p: Dict[str, Any], u: jax.Array,
                    rotary: bool = False) -> jax.Array:
    s = u.shape[0]
    heads, kv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
    qkv = u @ p["w_qkv"]
    q = qkv[:, :heads * hd].reshape(s, heads, hd)
    k = qkv[:, heads * hd:(heads + kv) * hd].reshape(s, kv, hd)
    v = qkv[:, (heads + kv) * hd:].reshape(s, kv, hd)
    if rotary:
        q, k = _rope(q, 10000.0), _rope(k, 10000.0)
    q = q.reshape(s, kv, heads // kv, hd)
    pos = jnp.arange(s)
    causal = pos[None, :] <= pos[:, None]
    sc = cfg.attention_multiplier * jnp.einsum("tgrd,sgd->grts", q, k)
    w = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    o = jnp.einsum("grts,sgd->tgrd", w, v).reshape(s, heads * hd)
    return o @ p["w_o"]


def forward(params: Dict[str, Any], cfg, tokens: jax.Array, *,
            state_resets: Optional[jax.Array] = None,
            rotary: bool = False) -> jax.Array:
    """Logits [S, V] after each of the user's ``tokens`` [S]."""
    f32 = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                 params)
    eps, c = cfg.rms_norm_eps, cfg.residual_multiplier
    x = cfg.embedding_multiplier * f32["embed"][tokens]
    for kind, p in zip(cfg.layer_types, f32["layers"]):
        u = rms(x, p["mixer_norm"], eps)
        if kind == "mamba":
            out = mamba2_mixer(cfg, p, u, state_resets)
        else:
            out = attention_mixer(cfg, p, u, rotary)
        x = x + c * out
        x = x + c * mlp(p, rms(x, p["ffn_norm"], eps))
    h = rms(x, f32["final_norm"], eps)
    return h @ f32["embed"].T / cfg.logits_scaling
