"""The plain reference of the Mamba-2 / no-position attention cells: a
user's WHOLE event history through every layer at every position in
straightforward ``jax.numpy`` at float32 ``highest``, with no cache, no
batching of turns, no tile and no kernel, importing nothing of the
program and taking nothing it made.  One layer's weights are re-made from
the seed at a time (``datagen_granite_h``).

Equations (``d`` = 2,048; 40 layers, attention at 5, 15, 25, 35; ``RMS``
with eps 1e-5; the head tied; no positional encoding): ``x_0 = 12
E[item]``; every layer ``h = x + 0.22 Mixer(RMS(x))``, ``y = h + 0.22
MLP(RMS(h))``, ``MLP(u) = (silu(a) * b) W_2``, ``[a | b] = u W_13``;
``logits = RMS(x) E^T / 8``.

* Mamba-2 (``E`` = 4,096 = 64 heads of 64, ``N`` = 128, one group):
  ``[z | xBC | dt] = u W_in``; ``xBC_t = silu(sum_j w_conv[j] xBC_{t-3+j}
  + b_conv)``; ``[x | B | C] = xBC``; ``dt = softplus(dt + dt_bias)``;
  ``A = -exp(A_log)``; ``S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t[h] (x)
  B_t``, ``y_t[h] = S_t C_t + D_h x_t[h]``; ``y = RMS_E(y * silu(z))
  g``; ``out = y W_out``.  The recurrence is computed in BLOCKS of
  ``SSD_BLOCK`` events (:func:`ssd`): the same sums written as a masked
  product inside a block (``y_t = sum_{s<=t} (C_t . B_s) exp(cs_t - cs_s)
  dt_s x_s + exp(cs_t) S_in C_t``, ``cs`` the running sum of ``dt A_h``),
  the state carried between blocks.  An event-by-event scan over a 2 MiB
  carry for 36 layers and ~10K events a resident would not end inside a
  check; ``tests/test_granite_h.py`` holds the block form to that scan.
* Attention: ``[q | k | v] = u W_qkv`` (32 / 8 / 8 heads of 64), query
  head ``i`` reads kv head ``i // 4``; causal softmax of ``0.015625 q
  k^T``, a plain mask over the score matrix, ``ROWS`` queries at a time so
  that it fits; ``out = o W_o``.

The CONTROLS: ``weight_dtype`` (the weights rounded one step below
bfloat16), ``turn_starts`` (per sequence, positions before which the
Mamba-2 state is zeroed: a turn that starts from nothing),
``attention_multiplier`` (1 / 8 is ``1 / sqrt(head size)``, what the
config does NOT say), ``residual_multiplier`` (1: the multiplier left
out).  ``compare_granite_h`` must refuse each.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import datagen_granite_h as gen

BLOCK = 4096      # rows an MLP takes at a time
SSD_BLOCK = 256   # events of the recurrence's masked product
# A user's rows are padded to one of these (multiples of BLOCK; past the
# last, to a multiple of BLOCK): a program's shapes then depend on that
# size alone, so the compile cache holds a program a size and kind of
# mixer (what tells layers apart is passed as numbers).
ROW_MENU = (4096, 8192, 16384, 24576)
ROWS = 128        # queries of an attention layer taken at a time
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


def _ssd_block(state, turn_in, x, dt, b, c, turn, a):
    """One block of the recurrence: ``x`` [Q, H, P], ``dt`` [Q, H], ``b``,
    ``c`` [Q, N], ``turn`` [Q] (events of one turn share a number; the
    state is zeroed where it changes), ``a`` [H]; ``state`` [H, P, N] and
    ``turn_in`` as the block before left them -> (y [Q, H, P] without the
    skip term, the state after the block)."""
    q = x.shape[0]
    cs = jnp.cumsum(dt * a, axis=0)                         # [Q, H]
    pos = jnp.arange(q)
    seen = (pos[None, :] <= pos[:, None]) & (turn[None, :] == turn[:, None])
    decay = jnp.where(seen[None], jnp.exp(jnp.minimum(
        cs.T[:, :, None] - cs.T[:, None, :], 0.0)), 0.0)    # [H, t, s]
    g = jnp.einsum("tn,sn->ts", c, b, precision=_HI)
    xd = dt[:, :, None] * x
    inside = jnp.einsum("hts,shp->thp", g[None] * decay, xd, precision=_HI)
    carried = (turn == turn_in)[:, None, None]
    before = jnp.exp(cs)[:, :, None] * jnp.einsum(
        "tn,hpn->thp", c, state, precision=_HI)
    last = (turn == turn[-1])[:, None, None]
    w = jnp.where(last, jnp.exp(cs[-1][None] - cs)[:, :, None] * xd, 0.0)
    state = jnp.where(turn[-1] == turn_in, jnp.exp(cs[-1])[:, None, None]
                      * state, 0.0) \
        + jnp.einsum("shp,sn->hpn", w, b, precision=_HI)
    return inside + jnp.where(carried, before, 0.0), state


def _ssd_scan(state, turn_in, x, dt, b, c, turn, a, block: int):
    """:func:`_ssd_block` over ``x`` [S, H, P] (``S`` a multiple of
    ``block``), a block at a time from ``state`` and ``turn_in`` -> (y,
    the state and the turn after the last event)."""
    def part(carry, rows):
        y, state = _ssd_block(*carry, *rows, a)
        return (state, rows[-1][-1]), y

    split = lambda v: v.reshape(  # noqa: E731
        (v.shape[0] // block, block) + v.shape[1:])
    (state, turn_in), y = jax.lax.scan(
        part, (state, turn_in), tuple(split(v) for v in (x, dt, b, c, turn)))
    return y.reshape(x.shape), state, turn_in


def ssd(x, dt, b, c, a, turn, block: int = SSD_BLOCK):
    """The Mamba-2 recurrence over ``x`` [S, H, P] (``S`` a multiple of
    ``block``) from a zero state, a block at a time -> y [S, H, P] without
    the skip term."""
    state = jnp.zeros(x.shape[1:] + b.shape[-1:], jnp.float32)
    return _ssd_scan(state, turn[0], x, dt, b, c, turn, a, block)[0]


@functools.partial(jax.jit, static_argnames=("eps", "sz"),
                   donate_argnums=(0,))
def _mamba_user(flat, w, turn, scale, *, eps, sz):
    """One user's rows [rows, d] through a Mamba-2 mixer, ``BLOCK`` rows
    at a time with the state, the turn and the convolution's last rows
    carried over."""
    e, n, heads, hp, cw = sz
    a = -jnp.exp(w["a_log"])
    wide = e + 2 * n

    def part(i, carry):
        flat, state, turn_in, tail = carry
        at = i * BLOCK
        x = jax.lax.dynamic_slice_in_dim(flat, at, BLOCK)
        tn = jax.lax.dynamic_slice_in_dim(turn, at, BLOCK)
        proj = _dot(_rms(x, w["mixer_norm"], eps), w["w_in"])
        z = proj[:, :e]
        padded = jnp.concatenate([tail, proj[:, e:e + wide]], axis=0)
        conv = sum(w["conv_w"][j] * padded[j:j + BLOCK] for j in range(cw))
        xbc = jax.nn.silu(conv + w["conv_b"])
        dt = jax.nn.softplus(proj[:, e + wide:] + w["dt_b"])
        xs = xbc[:, :e].reshape(BLOCK, heads, hp)
        y, state, turn_in = _ssd_scan(
            state, turn_in, xs, dt, xbc[:, e:e + n], xbc[:, e + n:], tn, a,
            SSD_BLOCK)
        y = y + w["d_skip"][:, None] * xs
        y = _rms(y.reshape(BLOCK, e) * jax.nn.silu(z), w["gate_norm"], eps)
        flat = jax.lax.dynamic_update_slice_in_dim(
            flat, x + scale * _dot(y, w["w_out"]), at, 0)
        return flat, state, turn_in, padded[BLOCK:]

    flat, _, _, _ = jax.lax.fori_loop(
        0, flat.shape[0] // BLOCK, part,
        (flat, jnp.zeros((heads, hp, n), jnp.float32), turn[0],
         jnp.zeros((cw - 1, wide), jnp.float32)))
    return flat


@functools.partial(jax.jit, static_argnames=("eps", "sz"),
                   donate_argnums=(0,))
def _attention_user(x, w, multiplier, scale, *, eps, sz):
    """One user's rows [rows, d] through an attention mixer: the plain
    causal mask over the score matrix, ``ROWS`` queries at a time.
    ``multiplier`` scales the scores and ``scale`` the residual branch
    (numbers, not shapes: one program serves the controls too)."""
    heads, kv, hd = sz
    rows = x.shape[0]
    qkv = _dot(_rms(x, w["mixer_norm"], eps), w["w_qkv"])
    q = qkv[:, :heads * hd].reshape(rows, kv, heads // kv, hd)
    k = qkv[:, heads * hd:(heads + kv) * hd].reshape(rows, kv, hd)
    v = qkv[:, (heads + kv) * hd:].reshape(rows, kv, hd)

    def block(lo):
        qb = jax.lax.dynamic_slice_in_dim(q, lo, ROWS)
        mask = jnp.arange(rows)[None, :] <= (lo + jnp.arange(ROWS))[:, None]
        s = multiplier * jnp.einsum("tgrd,sgd->grts", qb, k, precision=_HI)
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("grts,sgd->tgrd", p, v, precision=_HI
                          ).reshape(ROWS, heads * hd)

    o = jax.lax.map(block, jnp.arange(0, rows, ROWS)).reshape(
        rows, heads * hd)
    return x + scale * _dot(o, w["w_o"])


@functools.partial(jax.jit, static_argnames=("eps",), donate_argnums=(0,))
def _mlp(flat, w, scale, *, eps):
    f = w["w2"].shape[0]

    def body(i, flat):
        x = jax.lax.dynamic_slice_in_dim(flat, i * BLOCK, BLOCK)
        h = _dot(_rms(x, w["ffn_norm"], eps), w["w13"])
        y = _dot(jax.nn.silu(h[:, :f]) * h[:, f:], w["w2"])
        return jax.lax.dynamic_update_slice_in_dim(
            flat, x + scale * y, i * BLOCK, 0)

    return jax.lax.fori_loop(0, flat.shape[0] // BLOCK, body, flat)


def logits_at(config: Dict[str, Any], seed: int,
              sequences: Sequence[np.ndarray],
              positions: Sequence[Sequence[int]], *, weight_dtype=None,
              turn_starts: Optional[Sequence[Sequence[int]]] = None,
              attention_multiplier: Optional[float] = None,
              residual_multiplier: Optional[float] = None,
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
    s = gen.sizes(config)
    multiplier = float(config["attention_multiplier"]
                       if attention_multiplier is None
                       else attention_multiplier)
    scale = float(config["residual_multiplier"]
                  if residual_multiplier is None else residual_multiplier)
    # Every array is ONE user's, of a size from the menu.
    rows = [next((m for m in ROW_MENU if len(q) <= m),
                 -(-len(q) // BLOCK) * BLOCK) for q in sequences]
    embed = _f32({"e": gen.embedding(config, seed)}, weight_dtype)["e"]
    flats, turns = [], []
    for i, (seq, n) in enumerate(zip(sequences, rows)):
        tokens = np.zeros(n, np.int32)
        tokens[:len(seq)] = seq
        flats.append(float(config["embedding_multiplier"])
                     * embed[jnp.asarray(tokens)])
        starts = np.zeros(n, np.int32)
        if turn_starts is not None:
            starts[np.asarray(turn_starts[i], np.int64)] = 1
        turns.append(jnp.asarray(np.cumsum(starts), jnp.int32))
    del embed
    for layer, kind in enumerate(config["layer_types"]):
        w = lap("weights", _f32(gen.layer_weights(config, seed, layer),
                                weight_dtype))
        ffn = {k: w.pop(k) for k in ("ffn_norm", "w13", "w2")}
        for u in range(len(rows)):
            if kind == gen.MAMBA:
                flats[u] = _mamba_user(
                    flats[u], w, turns[u], scale, eps=eps,
                    sz=(s["e"], s["n"], s["h"], s["p"], s["w"]))
            else:
                flats[u] = _attention_user(
                    flats[u], w, multiplier, scale, eps=eps,
                    sz=(s["heads"], s["kv"], s["hd"]))
        lap("mixers", flats)
        flats = lap("mlp", [_mlp(f, ffn, scale, eps=eps) for f in flats])
        del w, ffn
    head = _f32({"h": gen.embedding(config, seed)}, weight_dtype)["h"]
    norm = gen.final_norm(config, seed)
    out = []
    for flat, pos in zip(flats, positions):
        h = _rms(flat[jnp.asarray(np.asarray(pos, np.int64))], norm, eps)
        out.append(np.asarray(_dot(h, head.T))
                   / float(config["logits_scaling"]))
    lap("head", out[-1] if out else flats)
    return out
