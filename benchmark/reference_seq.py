"""The plain reference of the sequence cells: a user's WHOLE event
history through every held layer in straightforward ``jax.numpy`` at
float32 ``highest``, with no cache, no batching of turns, no kernel,
importing nothing of the program and taking nothing it made.  One
layer's weights are re-made from the seed at a time (``datagen_seq``).

Equations (``d`` hidden size, ``RMS(x; g) = x / sqrt(mean(x^2) + eps)
* g``): every layer ``h = x + Op(RMS(x; g_op))``, ``y = h + FF(RMS(h;
g_ffn))``; after the last held layer one more ``RMS``, then ``logits =
h E^T`` (``E`` the embedding, tied).

* ``Op`` conv: ``[B, C, X] = split3(u W_in)``; ``z = B * X``; ``c_t =
  sum_j w_j * z_{t-(L-1)+j}`` (zero before the first event); ``(C * c)
  W_out``.
* ``Op`` attention: ``q, k, v`` = column blocks of ``u W_qkv``; RMS over
  each head of ``q`` and ``k``; rotate-half rotary at the event's index;
  causal ``softmax(q k^T / sqrt(hd)) v``, four query heads to a kv head;
  ``W_o``.
* ``FF`` dense: ``(silu(u W1) * (u W3)) W2``.  ``FF`` experts: ``s =
  sigmoid(u W_g)``; the top-k of ``s + b`` are picked; weights ``s_e /
  (sum of the picked + 1e-6)`` times ``routed_scaling_factor``; each
  expert's MLP runs on the tokens that picked it.

How it fits the chip: the users' sequences lie back to back in one
[N, d] array, each padded to a multiple of ``BLOCK`` rows (both mixers
are causal, so padding after a user's last event changes nothing before
it); a mixer runs on one user's rows at a time; the experts run one at a
time on the rows that picked them, gathered up to the busiest expert's
count.

``weight_dtype`` exists for the CONTROL: the weights rounded one step
below bfloat16, which ``compare_seq`` must refuse.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import datagen_seq

BLOCK = 1024
ROUTER_EPS = 1e-6
_HI = jax.lax.Precision.HIGHEST


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _dot(a, b):
    return jnp.dot(a, b, precision=_HI)


def _f32(w: Dict[str, jax.Array], weight_dtype) -> Dict[str, jax.Array]:
    out = {}
    for name, a in w.items():
        if weight_dtype is not None and a.dtype == jnp.bfloat16:
            a = a.astype(weight_dtype)
        out[name] = a.astype(jnp.float32)
    return out


@functools.partial(jax.jit, static_argnames=("rows", "eps", "taps"),
                   donate_argnums=(0,))
def _conv_user(flat, start, w, *, rows, eps, taps):
    d = flat.shape[1]
    x = jax.lax.dynamic_slice_in_dim(flat, start, rows)
    u = _rms(x, w["op_norm"], eps)
    bcx = _dot(u, w["w_in"])
    z = bcx[:, :d] * bcx[:, 2 * d:]
    zp = jnp.concatenate([jnp.zeros((taps - 1, d), z.dtype), z])
    c = sum(w["conv_w"][j] * zp[j:j + rows] for j in range(taps))
    out = _dot(bcx[:, d:2 * d] * c, w["w_out"])
    return jax.lax.dynamic_update_slice_in_dim(flat, x + out, start, 0)


def _rope(x, theta):
    s, _, hd = x.shape
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


@functools.partial(jax.jit, static_argnames=("rows", "eps", "heads",
                                             "kv_heads", "theta"),
                   donate_argnums=(0,))
def _attention_user(flat, start, w, *, rows, eps, heads, kv_heads, theta):
    d = flat.shape[1]
    hd = d // heads
    x = jax.lax.dynamic_slice_in_dim(flat, start, rows)
    u = _rms(x, w["op_norm"], eps)
    qkv = _dot(u, w["w_qkv"])
    q = qkv[:, :d].reshape(rows, heads, hd)
    k = qkv[:, d:d + kv_heads * hd].reshape(rows, kv_heads, hd)
    v = qkv[:, d + kv_heads * hd:].reshape(rows, kv_heads, hd)
    q = _rope(_rms(q, w["q_norm"], eps), theta)
    k = _rope(_rms(k, w["k_norm"], eps), theta)
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    def block(lo):
        # A block of queries at a time, so the scores fit.
        qb = jax.lax.dynamic_slice_in_dim(q, lo, BLOCK)
        s = jnp.einsum("thd,shd->hts", qb, k, precision=_HI) / math.sqrt(hd)
        causal = (jnp.arange(rows)[None, :]
                  <= (lo + jnp.arange(BLOCK))[:, None])
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", p, v,
                          precision=_HI).reshape(BLOCK, d)

    outs = jax.lax.map(block, jnp.arange(0, rows, BLOCK))
    out = _dot(outs.reshape(rows, d), w["w_o"])
    return jax.lax.dynamic_update_slice_in_dim(flat, x + out, start, 0)


@functools.partial(jax.jit, static_argnames=("eps",), donate_argnums=(0,))
def _dense_ff(flat, w, *, eps):
    f = w["w2"].shape[0]
    step = 8 * BLOCK

    def body(i, flat):
        x = jax.lax.dynamic_slice_in_dim(flat, i * step, step)
        h = _dot(_rms(x, w["ffn_norm"], eps), w["w13"])
        y = _dot(jax.nn.silu(h[:, :f]) * h[:, f:], w["w2"])
        return jax.lax.dynamic_update_slice_in_dim(flat, x + y, i * step, 0)

    return jax.lax.fori_loop(0, flat.shape[0] // step, body, flat)


@functools.partial(jax.jit, static_argnames=("eps", "top_k", "use_bias",
                                             "norm_topk", "scale"))
def _router(flat, valid, w, *, eps, top_k, use_bias, norm_topk, scale):
    """(normed tokens, [N, E] weight of each expert for each token, zero
    where the expert is not among the token's picks or the row is
    padding)."""
    u = _rms(flat, w["ffn_norm"], eps)
    s = jax.nn.sigmoid(_dot(u, w["w_g"]))
    _, ids = jax.lax.top_k(s + w["b"] if use_bias else s, top_k)
    chosen = jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], ids].set(1.0)
    g = s * chosen
    if norm_topk:
        g = g / (jnp.sum(g, -1, keepdims=True) + ROUTER_EPS)
    return u, g * scale * valid[:, None]


@functools.partial(jax.jit, static_argnames=("cap",), donate_argnums=(0,))
def _one_expert(acc, u, gate, w13, w2, *, cap):
    """``acc += gate * MLP(u)`` on the rows whose gate is not zero."""
    n, f = u.shape[0], w2.shape[0]
    idx = jnp.nonzero(gate > 0, size=cap, fill_value=n)[0]
    rows = jnp.concatenate([u, jnp.zeros((1, u.shape[1]), u.dtype)])[idx]
    h = _dot(rows, w13)
    y = _dot(jax.nn.silu(h[:, :f]) * h[:, f:], w2)
    y = y * jnp.concatenate([gate, jnp.zeros(1, gate.dtype)])[idx][:, None]
    return acc.at[idx].add(y, mode="drop")


def logits_at_end(config: Dict[str, Any], seed: int,
                  sequences: Sequence[np.ndarray], *,
                  weight_dtype=None, timings=None) -> np.ndarray:
    """[n, V] float32 logits after each sequence's last event.
    ``timings``: a dict that is given the seconds of each part (tools)."""
    import time

    def lap(name, value):
        if timings is not None:
            jax.block_until_ready(value)
            now = time.perf_counter()
            timings[name] = timings.get(name, 0.0) + now - lap.at
            lap.at = now
        return value

    lap.at = time.perf_counter()
    eps = float(config["norm_eps"])
    heads = int(config["num_attention_heads"])
    kv_heads = int(config["num_key_value_heads"])
    theta = float((config.get("rope_parameters") or {}).get(
        "rope_theta", 1e6))
    rows = [-(-max(len(s), 1) // BLOCK) * BLOCK for s in sequences]
    starts = np.concatenate([[0], np.cumsum(rows)]).astype(np.int64)
    # Few distinct sizes over the seeds, so compiled programs are found
    # again: the whole to a multiple of 32 blocks.
    total = int(-(-starts[-1] // (32 * BLOCK)) * (32 * BLOCK))
    tokens = np.zeros(total, np.int32)
    valid = np.zeros(total, np.float32)
    for seq, at in zip(sequences, starts):
        tokens[at:at + len(seq)] = seq
        valid[at:at + len(seq)] = 1.0
    embed = _f32({"e": datagen_seq.embedding(config, seed)},
                 weight_dtype)["e"]
    flat = embed[jnp.asarray(tokens)]
    valid = jnp.asarray(valid)
    for layer in datagen_seq.held_layers(config):
        w = lap("weights", _f32(datagen_seq.layer_weights(
            config, seed, layer), weight_dtype))
        mixer = {k: v for k, v in w.items()
                 if k not in ("w13", "w2", "w_g", "b", "ffn_norm")}
        for at, n in zip(starts, rows):
            if config["layer_types"][layer] == "conv":
                flat = _conv_user(flat, int(at), mixer, rows=n, eps=eps,
                                  taps=int(config["conv_L_cache"]))
            else:
                flat = _attention_user(flat, int(at), mixer, rows=n,
                                       eps=eps, heads=heads,
                                       kv_heads=kv_heads, theta=theta)
        lap("mixers", flat)
        if datagen_seq.is_dense(config, layer):
            flat = _dense_ff(flat, {k: w[k] for k in
                                    ("ffn_norm", "w13", "w2")}, eps=eps)
        else:
            u, gates = _router(
                flat, valid, {k: w[k] for k in ("ffn_norm", "w_g", "b")},
                eps=eps, top_k=int(config["num_experts_per_tok"]),
                use_bias=bool(config["use_expert_bias"]),
                norm_topk=bool(config["norm_topk_prob"]),
                scale=float(config["routed_scaling_factor"]))
            counts = np.asarray(jnp.sum(gates > 0, axis=0))
            lap("router", gates)
            for e in range(int(config["num_experts"])):
                # The expert's rows, to a multiple of 8 blocks (few
                # distinct sizes, so few programs).
                cap = -(-max(int(counts[e]), 1) // (8 * BLOCK)) * (8 * BLOCK)
                flat = _one_expert(flat, u, gates[:, e], w["w13"][e],
                                   w["w2"][e], cap=min(cap, total))
        lap("ff", flat)
        del w
    last = jnp.asarray([at + max(len(s), 1) - 1
                        for s, at in zip(sequences, starts)])
    h = _rms(flat[last], _f32({"g": datagen_seq.final_norm(config, seed)},
                              None)["g"], eps)
    return np.asarray(_dot(h, embed.T))
