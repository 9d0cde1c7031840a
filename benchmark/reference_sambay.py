"""The plain reference of the Mamba / sliding-window / shared-cache cells:
a user's WHOLE event history through every layer at every position in
straightforward ``jax.numpy`` at float32 ``highest``, with no cache, no
batching of turns, no kernel and no decoder split, importing nothing of
the program and taking nothing it made.  One layer's weights are re-made
from the seed at a time (``datagen_sambay``).

Equations (``d`` hidden size; ``LN`` = LayerNorm with gain and bias, eps
``layer_norm_eps``; 32 layers; the head tied; no positional encoding):
every layer ``h = x + Mixer(LN(x))``, ``y = h + MLP(LN(h))``, ``MLP(u) =
(silu(u W_g) * (u W_v)) W_2``; after the last layer one ``LN``, then
``logits = h E^T``.

* Mamba (even layers up to 16): ``[x, z] = u W_in``; ``xc_t =
  silu(sum_j w_conv[j] x_{t-3+j} + b_conv)``; ``[r, B, C] = xc W_x``;
  ``Delta = softplus(r W_dt + b_dt)``; the recurrence itself, an event at
  a time: ``h_t = exp(Delta_t (x) A) h_{t-1} + (Delta_t xc_t) (x) B_t``,
  ``y_t = h_t C_t + D xc_t``, ``A = -exp(A_log)``; ``out = (y silu(z))
  W_out``; layer 16's ``y`` is the memory ``m``.
* Differential attention (odd layers): query heads ``2i, 2i + 1`` are
  the pair ``(q1_i, q2_i)``, kv heads ``2j, 2j + 1`` give ``k1_j, k2_j``
  and ``V_j = [v_2j, v_2j+1]``, ``j = i // 2``; ``O_i = RMS_128((softmax(
  q1 k1^T / 8) - lambda softmax(q2 k2^T / 8)) V_j; g_sub) (1 -
  lambda_init)``, ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) +
  lambda_init``, ``lambda_init = 0.8 - 0.6 exp(-0.3 l)``; ``out = O W_o +
  b_o``.  The mask is a plain mask over the score matrix, taken ``ROWS``
  queries at a time so that it fits: causal, and below layer 16 also ``t
  - s < sliding_window``.  Layer 17's ``k, v`` are kept; layers 19, 21,
  .. 31 project a query only and attend them.
* Gated memory unit (even layers from 18): ``out = (silu(u W_in) * m)
  W_out``, ``m`` of the same event.

The CONTROLS: ``weight_dtype`` (the weights rounded one step below
bfloat16), ``zero_lambda`` (``lambda = 0``), ``window`` (another reach:
384 is a page released one too early), ``turn_starts`` (per sequence,
positions before which the scan's state is zeroed: a turn that starts
from nothing).  ``compare_sambay`` must refuse each.
"""

from __future__ import annotations

import functools
import math
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import datagen_sambay as gen

BLOCK = 4096      # rows a Mamba layer, a GMU and an MLP take at a time
# A user's rows are padded to one of these (multiples of BLOCK; past the
# last, to a multiple of BLOCK): four sizes over every seed's residents,
# so 28 programs in all (a program a size and kind of mixer, not a
# layer: what tells layers apart is passed as numbers), which the compile
# cache then holds.  A program takes 5-8 s to compile and the whole pass
# ~10 s to run (my chip runs, PR 37).
ROW_MENU = (4096, 8192, 16384, 36864)
ROWS = 128        # queries of an attention layer taken at a time
_HI = jax.lax.Precision.HIGHEST


def _ln(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _dot(a, b):
    return jnp.dot(a, b, precision=_HI)


def _f32(w: Dict[str, jax.Array], weight_dtype) -> Dict[str, jax.Array]:
    out = {}
    for name, a in w.items():
        if weight_dtype is not None and a.dtype == jnp.bfloat16:
            a = a.astype(weight_dtype)
        out[name] = a.astype(jnp.float32)
    return out


@functools.partial(jax.jit, static_argnames=("eps", "sz", "keep"),
                   donate_argnums=(0, 1))
def _mamba_user(flat, memory, w, resets, *, eps, sz, keep):
    """One user's rows [rows, d] through a Mamba mixer, ``BLOCK`` at a
    time with the state and the convolution's last rows carried over; with
    ``keep`` the scan's output goes to ``memory`` (else that is passed
    through).  ``resets`` [rows] bool: the state is zeroed before that
    event."""
    e, n, r, cw = sz
    a = -jnp.exp(w["a_log"])

    def event(h, row):
        xt, dt, b, c, reset = row
        h = jnp.where(reset, 0.0, h)
        h = jnp.exp(dt[None, :] * a) * h + (dt * xt)[None, :] * b[:, None]
        return h, jnp.sum(h * c[:, None], axis=0) + w["d_skip"] * xt

    def part(i, carry):
        flat, memory, h, tail = carry
        at = i * BLOCK
        x = jax.lax.dynamic_slice_in_dim(flat, at, BLOCK)
        xz = _dot(_ln(x, w["norm1_g"], w["norm1_b"], eps), w["w_in"])
        xin, z = xz[:, :e], xz[:, e:]
        padded = jnp.concatenate([tail, xin], axis=0)
        conv = sum(w["conv_w"][j] * padded[j:j + BLOCK] for j in range(cw))
        xc = jax.nn.silu(conv + w["conv_b"])
        proj = _dot(xc, w["w_x"])
        delta = jax.nn.softplus(_dot(proj[:, :r], w["w_dt"]) + w["dt_b"])
        h, y = jax.lax.scan(event, h, (
            xc, delta, proj[:, r:r + n], proj[:, r + n:],
            jax.lax.dynamic_slice_in_dim(resets, at, BLOCK)))
        out = _dot(y * jax.nn.silu(z), w["w_out"])
        flat = jax.lax.dynamic_update_slice_in_dim(flat, x + out, at, 0)
        if keep:
            memory = jax.lax.dynamic_update_slice_in_dim(memory, y, at, 0)
        return flat, memory, h, padded[BLOCK:]

    flat, memory, _, _ = jax.lax.fori_loop(
        0, flat.shape[0] // BLOCK, part,
        (flat, memory, jnp.zeros((n, e), jnp.float32),
         jnp.zeros((cw - 1, e), jnp.float32)))
    return flat, memory


@functools.partial(jax.jit, static_argnames=("eps", "sz", "kind", "reach"),
                   donate_argnums=(0, 1))
def _attention_user(x, shared, w, li, lam, window, *, eps, sz, kind, reach):
    """One user's rows [rows, d] through a differential-attention mixer.
    ``shared`` [rows, 2 kv hd]: the full layer writes its keys and values
    there, a cross layer reads them.  ``li`` = lambda_init, ``lam`` =
    lambda, ``window`` the window layers' reach (numbers, not shapes: one
    program serves every layer of a kind); ``reach`` the longest window
    asked for, which sizes the band of keys a block of queries is
    given."""
    heads, kv, hd = sz
    pairs, per = kv // 2, heads // kv
    qw = heads * hd
    rows = x.shape[0]
    u = _ln(x, w["norm1_g"], w["norm1_b"], eps)
    if kind == gen.CROSS:
        q = _dot(u, w["w_q"]) + w["b_q"]
        kvs = shared
    else:
        qkv = _dot(u, w["w_qkv"]) + w["b_qkv"]
        q, kvs = qkv[:, :qw], qkv[:, qw:]
        if kind == gen.FULL:
            shared = kvs
    q = q.reshape(rows, pairs, per, 2, hd)
    k = kvs[:, :kv * hd].reshape(rows, pairs, 2, hd)
    v = kvs[:, kv * hd:].reshape(rows, pairs, 2 * hd)
    # A window layer's block of queries is given the events its mask can
    # let through (the ``back`` before the block, and the block) and no
    # others; the mask itself is the plain one, on positions.
    back = -(-reach // ROWS) * ROWS if kind == gen.WINDOW else 0
    if back:
        k = jnp.pad(k, ((back, 0), (0, 0), (0, 0), (0, 0)))
        v = jnp.pad(v, ((back, 0), (0, 0), (0, 0)))

    def block(lo):
        qb = jax.lax.dynamic_slice_in_dim(q, lo, ROWS)
        pos = lo + jnp.arange(ROWS)
        if back:
            kb = jax.lax.dynamic_slice_in_dim(k, lo, back + ROWS)
            vb = jax.lax.dynamic_slice_in_dim(v, lo, back + ROWS)
            at = lo - back + jnp.arange(back + ROWS)
            mask = (at[None, :] >= 0) & (at[None, :] <= pos[:, None]) \
                & (pos[:, None] - at[None, :] < window)
        else:
            kb, vb = k, v
            mask = jnp.arange(rows)[None, :] <= pos[:, None]

        def branch(b):
            s = jnp.einsum("tgrd,sgd->grts", qb[:, :, :, b], kb[:, :, b],
                           precision=_HI) / math.sqrt(hd)
            return jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)

        o = jnp.einsum("grts,sge->tgre", branch(0) - lam * branch(1), vb,
                       precision=_HI)
        o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + eps) \
            * w["sub_g"] * (1.0 - li)
        return o.reshape(ROWS, qw)

    o = jax.lax.map(block, jnp.arange(0, rows, ROWS)).reshape(rows, qw)
    return x + _dot(o, w["w_o"]) + w["b_o"], shared


@functools.partial(jax.jit, static_argnames=("eps",), donate_argnums=(0,))
def _gmu(flat, memory, w, *, eps):
    def body(i, flat):
        x = jax.lax.dynamic_slice_in_dim(flat, i * BLOCK, BLOCK)
        m = jax.lax.dynamic_slice_in_dim(memory, i * BLOCK, BLOCK)
        u = _ln(x, w["norm1_g"], w["norm1_b"], eps)
        out = _dot(jax.nn.silu(_dot(u, w["w_in"])) * m, w["w_out"])
        return jax.lax.dynamic_update_slice_in_dim(flat, x + out,
                                                   i * BLOCK, 0)

    return jax.lax.fori_loop(0, flat.shape[0] // BLOCK, body, flat)


@functools.partial(jax.jit, static_argnames=("eps",), donate_argnums=(0,))
def _mlp(flat, w, *, eps):
    f = w["w2"].shape[0]

    def body(i, flat):
        x = jax.lax.dynamic_slice_in_dim(flat, i * BLOCK, BLOCK)
        h = _dot(_ln(x, w["norm2_g"], w["norm2_b"], eps), w["w13"])
        y = _dot(jax.nn.silu(h[:, :f]) * h[:, f:], w["w2"])
        return jax.lax.dynamic_update_slice_in_dim(flat, x + y, i * BLOCK, 0)

    return jax.lax.fori_loop(0, flat.shape[0] // BLOCK, body, flat)


def logits_at(config: Dict[str, Any], seed: int,
              sequences: Sequence[np.ndarray],
              positions: Sequence[Sequence[int]], *, weight_dtype=None,
              zero_lambda: bool = False, window: Optional[int] = None,
              turn_starts: Optional[Sequence[Sequence[int]]] = None,
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
    eps = float(config["layer_norm_eps"])
    s = gen.sizes(config)
    kinds = gen.kinds(config)
    memory_layer = len(kinds) // 2
    longest = int(config["sliding_window"])
    reach = longest if window is None else int(window)
    if reach > longest:
        raise ValueError("a control's window is within the configuration's")
    # Every array is ONE user's, of a size from the menu: a program's
    # shapes then depend on that size alone, never on who else is read.
    rows = [next((m for m in ROW_MENU if len(q) <= m),
                 -(-len(q) // BLOCK) * BLOCK) for q in sequences]
    embed = _f32({"e": gen.embedding(config, seed)}, weight_dtype)["e"]
    flats, resets = [], []
    for i, (seq, n) in enumerate(zip(sequences, rows)):
        tokens = np.zeros(n, np.int32)
        tokens[:len(seq)] = seq
        flats.append(embed[jnp.asarray(tokens)])
        starts = np.zeros(n, bool)
        if turn_starts is not None:
            starts[np.asarray(turn_starts[i], np.int64)] = True
        resets.append(jnp.asarray(starts))
    del embed
    memories = [jnp.zeros((n, s["e"]), jnp.float32) for n in rows]
    shareds = [jnp.zeros((n, 2 * s["kv"] * s["hd"]), jnp.float32)
               for n in rows]
    for layer, kind in enumerate(kinds):
        w = lap("weights", _f32(gen.layer_weights(config, seed, layer),
                                weight_dtype))
        mixer = {k: v for k, v in w.items()
                 if k not in ("w13", "w2", "norm2_g", "norm2_b")}
        ffn = {k: w[k] for k in ("norm2_g", "norm2_b", "w13", "w2")}
        if kind not in (gen.MAMBA, gen.GMU):
            li = 0.8 - 0.6 * math.exp(-0.3 * layer)
            lams = np.asarray(mixer["lam"], np.float64)
            lam = 0.0 if zero_lambda else float(
                np.exp(np.dot(lams[0], lams[1]))
                - np.exp(np.dot(lams[2], lams[3])) + li)
        for u in range(len(rows)):
            if kind == gen.MAMBA:
                flats[u], memories[u] = _mamba_user(
                    flats[u], memories[u], mixer, resets[u], eps=eps,
                    sz=(s["e"], s["n"], s["r"], s["w"]),
                    keep=layer == memory_layer)
            elif kind == gen.GMU:
                flats[u] = _gmu(flats[u], memories[u], mixer, eps=eps)
            else:
                flats[u], shareds[u] = _attention_user(
                    flats[u], shareds[u], mixer, li, lam, reach, eps=eps,
                    sz=(s["heads"], s["kv"], s["hd"]), kind=kind,
                    reach=longest)
        lap("mixers", flats)
        flats = lap("mlp", [_mlp(f, ffn, eps=eps) for f in flats])
        del w, mixer, ffn
    del memories, shareds
    head = _f32({"h": gen.embedding(config, seed)}, weight_dtype)["h"]
    norm = _f32(gen.final_norm(config, seed), None)
    out = []
    for flat, pos in zip(flats, positions):
        h = _ln(flat[jnp.asarray(np.asarray(pos, np.int64))],
                norm["final_g"], norm["final_b"], eps)
        out.append(np.asarray(_dot(h, head.T)))
    lap("head", out[-1] if out else flats)
    return out
