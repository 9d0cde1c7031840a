"""The plain reference of the block-selected / lightning cells: a user's
WHOLE event history through every held layer in straightforward
``jax.numpy`` at float32 ``highest``, with no cache, no batching of
turns, no kernel, importing nothing of the program and taking nothing it
made.  One layer's weights are re-made from the seed at a time
(``datagen_sala``).

Equations (``d`` hidden size, ``RMS_n(x; g) = x / sqrt(mean_n(x^2) +
eps) * g``, ``c = scale_depth / sqrt(32)``): ``x_0 = scale_emb E[item]``;
every layer ``h = x + c Mixer(RMS(x))``, ``y = h + c MLP(RMS(h))``,
``MLP(u) = (silu(u W_gate) * (u W_up)) W_down``; after the last held
layer one RMS, then ``logits = (h / (d / dim_model_base)) W_head^T``.

* ``lightning-attn``: ``q = rope(RMS_hd(u W_q))``, ``k = rope(RMS_hd(u
  W_k))``, ``v = u W_v``; the recurrence itself, an event at a time:
  ``S_t = lambda_h S_{t-1} + k_t^T v_t``, ``o_t = q_t S_t / sqrt(hd)``;
  ``out = (sigmoid(u W_z) * RMS_d(o)) W_o``; ``lambda_h = exp(-s_h (1 -
  l/31 + 1e-5))``, ``s_h = 2^(-8 (h + 1) / H)``, ``l`` the published
  layer index.
* ``minicpm4``: ``q = RMS_hd(u W_q)``, ``k = RMS_hd(u W_k)``, ``v = u
  W_v``, no rotary; the query at position ``p`` attends causally to all
  events if ``p + 1 <= dense_len``, else to the events of the blocks its
  group selects: pooled keys ``mean(k[16 j : 16 j + 32])`` of the windows
  that end at or before ``p``, ``softmax_j(q_h . K_j / sqrt(hd))`` summed
  over the group's heads, a block's score the widest of its windows
  ``4 b - 1 ... 4 b + 3``, block 0 and the 32 that end with the query's
  own forced, the 64 best taken.  The selection is a plain mask over the
  n x n score matrix, taken ``ROWS`` queries at a time so that it fits.
  ``out = (sigmoid(u W_z) * o) W_o``.

The CONTROLS: ``weight_dtype`` (the weights rounded one step below
bfloat16), ``forced_only`` (the selection left out: a query past
``dense_len`` reads its forced blocks alone), ``no_decay`` (``lambda =
1``).  ``compare_sala`` must refuse each.
"""

from __future__ import annotations

import functools
import math
import time
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import datagen_sala

BLOCK = 4096      # a user's rows are padded to a multiple of this: few
                  # distinct sizes over the seeds, so few programs
ROWS = 128        # queries of a sparse layer taken at a time
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


def _rope(x, theta, first):
    """Rotate-half rotary embedding of ``x`` [s, heads, hd], its first
    row at position ``first``."""
    s, _, hd = x.shape
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = (first + jnp.arange(s)).astype(jnp.float32)[:, None] \
        * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


@functools.partial(jax.jit, static_argnames=("rows", "eps", "heads", "theta",
                                             "c"), donate_argnums=(0,))
def _lightning_user(flat, start, w, rate, *, rows, eps, heads, theta, c):
    """The recurrence over one user's rows, an event at a time; the rows
    are taken ``BLOCK`` at a time (with the state carried over) only so
    that q, k and v of a long history fit."""
    hd = w["q_norm"].shape[0]
    lam = jnp.exp(-rate)[:, None, None]
    step = BLOCK

    def event(s, qkv_t):
        qt, kt, vt = qkv_t
        s = lam * s + kt[:, :, None] * vt[:, None, :]
        return s, jnp.einsum("hd,hde->he", qt, s, precision=_HI)

    def part(i, carry):
        flat, s = carry
        x = jax.lax.dynamic_slice_in_dim(flat, start + i * step, step)
        u = _rms(x, w["op_norm"], eps)
        qkv = _dot(u, w["w_qkv"]).reshape(step, 3, heads, hd)
        q = _rope(_rms(qkv[:, 0], w["q_norm"], eps), theta,
                  i * step) / math.sqrt(hd)
        k = _rope(_rms(qkv[:, 1], w["k_norm"], eps), theta, i * step)
        s, o = jax.lax.scan(event, s, (q, k, qkv[:, 2]))
        o = _rms(o.reshape(step, heads * hd), w["o_norm"], eps)
        out = _dot(jax.nn.sigmoid(_dot(u, w["w_z"])) * o, w["w_o"])
        return jax.lax.dynamic_update_slice_in_dim(
            flat, x + c * out, start + i * step, 0), s

    flat, _ = jax.lax.fori_loop(
        0, rows // step, part,
        (flat, jnp.zeros((heads, hd, hd), jnp.float32)))
    return flat


@functools.partial(jax.jit, static_argnames=(
    "rows", "eps", "heads", "kv_heads", "c", "sparse", "forced_only"),
    donate_argnums=(0,))
def _sparse_user(flat, start, w, *, rows, eps, heads, kv_heads, c, sparse,
                 forced_only):
    ks, st, bs, topk, init, window, dense_len = sparse
    d = flat.shape[1]
    hd = w["q_norm"].shape[0]
    x = jax.lax.dynamic_slice_in_dim(flat, start, rows)
    u = _rms(x, w["op_norm"], eps)
    qkv = _dot(u, w["w_qkv"])
    q = _rms(qkv[:, :heads * hd].reshape(rows, kv_heads, heads // kv_heads,
                                         hd), w["q_norm"], eps)
    k = _rms(qkv[:, heads * hd:(heads + kv_heads) * hd].reshape(
        rows, kv_heads, hd), w["k_norm"], eps)
    v = qkv[:, (heads + kv_heads) * hd:].reshape(rows, kv_heads, hd)
    per, nb = bs // st, rows // bs
    nj = (rows - ks) // st + 1
    pooled = jnp.mean(k[(jnp.arange(nj) * st)[:, None]
                        + jnp.arange(ks)[None, :]], axis=1)   # [nj, kv, hd]
    ends = jnp.arange(nj) * st + ks - 1
    b = jnp.arange(nb)
    every = jnp.arange(rows)

    def block(lo):
        qb = jax.lax.dynamic_slice_in_dim(q, lo, ROWS)
        pos = lo + jnp.arange(ROWS)
        bq = (pos // bs)[:, None]
        forced = (b[None, :] < init) | ((b[None, :] <= bq)
                                        & (b[None, :] > bq - window // bs))
        if forced_only:
            picked = jnp.broadcast_to(forced[None], (kv_heads, ROWS, nb))
        else:
            seen = (ends[None, :] <= pos[:, None])[None, None]
            s = jnp.einsum("tghd,jgd->ghtj", qb, pooled,
                           precision=_HI) / math.sqrt(hd)
            a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            a = jnp.where(seen, a, 0.0).sum(axis=1)       # [kv, ROWS, nj]
            pad = jnp.pad(a, ((0, 0), (0, 0), (1, nb * per - nj + per)))
            score = jnp.max(pad[:, :, (b * per)[:, None]
                                + jnp.arange(per + 1)[None, :]], axis=-1)
            score = jnp.where(forced[None], 1e30, score)
            score = jnp.where((b[None, :] <= bq)[None], score, -1e30)
            _, ids = jax.lax.top_k(score, topk)
            picked = jnp.zeros((kv_heads, ROWS, nb), bool).at[
                jnp.arange(kv_heads)[:, None, None],
                jnp.arange(ROWS)[None, :, None], ids].set(True)
        mask = (every[None, :] <= pos[:, None])[None] & (
            (pos + 1 <= dense_len)[None, :, None]
            | jnp.repeat(picked, bs, axis=2))
        s = jnp.einsum("tghd,sgd->ghts", qb, k,
                       precision=_HI) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(mask[:, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("ghts,sgd->tghd", p, v,
                          precision=_HI).reshape(ROWS, heads * hd)

    o = jax.lax.map(block, jnp.arange(0, rows, ROWS)).reshape(
        rows, heads * hd)
    out = _dot(jax.nn.sigmoid(_dot(u, w["w_z"])) * o, w["w_o"])
    return jax.lax.dynamic_update_slice_in_dim(flat, x + c * out, start, 0)


@functools.partial(jax.jit, static_argnames=("eps", "c"),
                   donate_argnums=(0,))
def _mlp(flat, w, *, eps, c):
    f = w["w2"].shape[0]
    step = BLOCK

    def body(i, flat):
        x = jax.lax.dynamic_slice_in_dim(flat, i * step, step)
        h = _dot(_rms(x, w["ffn_norm"], eps), w["w13"])
        y = _dot(jax.nn.silu(h[:, :f]) * h[:, f:], w["w2"])
        return jax.lax.dynamic_update_slice_in_dim(flat, x + c * y,
                                                   i * step, 0)

    return jax.lax.fori_loop(0, flat.shape[0] // step, body, flat)


def decay_rates(config: Dict[str, Any], layer: int) -> np.ndarray:
    h = int(config["lightning_nh"])
    slope = 2.0 ** (-8.0 * (np.arange(h) + 1.0) / h)
    published = int(config["published"]["num_hidden_layers"])
    return (slope * (1.0 - layer / (published - 1) + 1e-5)).astype(
        np.float32)


def logits_at(config: Dict[str, Any], seed: int,
              sequences: Sequence[np.ndarray],
              positions: Sequence[Sequence[int]], *, weight_dtype=None,
              forced_only: bool = False, no_decay: bool = False,
              timings=None) -> List[np.ndarray]:
    """For each sequence, the [len(positions[i]), V] float32 logits after
    its events at ``positions[i]`` (0-based).  ``timings``: a dict that is
    given the seconds of each part (tools)."""
    def lap(name, value):
        if timings is not None:
            jax.block_until_ready(value)
            now = time.perf_counter()
            timings[name] = timings.get(name, 0.0) + now - lap.at
            lap.at = now
        return value

    lap.at = time.perf_counter()
    eps = float(config["rms_norm_eps"])
    heads, kv_heads = (int(config["num_attention_heads"]),
                       int(config["num_key_value_heads"]))
    published = int(config["published"]["num_hidden_layers"])
    c = float(config["scale_depth"]) / math.sqrt(published)
    sp = config["sparse_config"]
    sparse = tuple(int(sp[k]) for k in (
        "kernel_size", "kernel_stride", "block_size", "topk", "init_blocks",
        "window_size", "dense_len"))
    rows = [-(-max(len(s), 1) // BLOCK) * BLOCK for s in sequences]
    starts = np.concatenate([[0], np.cumsum(rows)]).astype(np.int64)
    total = int(starts[-1])
    tokens = np.zeros(total, np.int32)
    for seq, at in zip(sequences, starts):
        tokens[at:at + len(seq)] = seq
    embed = _f32({"e": datagen_sala.vocab_matrix(config, seed, "embed")},
                 weight_dtype)["e"]
    flat = float(config["scale_emb"]) * embed[jnp.asarray(tokens)]
    del embed
    for layer in datagen_sala.held_layers(config):
        w = lap("weights", _f32(datagen_sala.layer_weights(
            config, seed, layer), weight_dtype))
        mixer = {k: v for k, v in w.items()
                 if k not in ("w13", "w2", "ffn_norm")}
        for at, n in zip(starts, rows):
            if config["mixer_types"][layer] == datagen_sala.LIGHTNING:
                rate = np.zeros(int(config["lightning_nh"]), np.float32) \
                    if no_decay else decay_rates(config, layer)
                flat = _lightning_user(
                    flat, int(at), mixer, jnp.asarray(rate), rows=n, eps=eps,
                    heads=int(config["lightning_nh"]),
                    theta=float(config["rope_theta"]), c=c)
            else:
                flat = _sparse_user(
                    flat, int(at), mixer, rows=n, eps=eps, heads=heads,
                    kv_heads=kv_heads, c=c, sparse=sparse,
                    forced_only=forced_only)
        lap("mixers", flat)
        flat = lap("mlp", _mlp(flat, {k: w[k] for k in
                                      ("ffn_norm", "w13", "w2")},
                               eps=eps, c=c))
        del w, mixer
    head = _f32({"h": datagen_sala.vocab_matrix(config, seed, "head")},
                weight_dtype)["h"]
    g = _f32({"g": datagen_sala.final_norm(config, seed)}, None)["g"]
    divisor = float(config["hidden_size"]) / float(config["dim_model_base"])
    out = []
    for at, pos in zip(starts, positions):
        h = _rms(flat[jnp.asarray(at + np.asarray(pos, np.int64))], g, eps)
        out.append(np.asarray(_dot(h / divisor, head.T)))
    lap("head", out[-1] if out else flat)
    return out
