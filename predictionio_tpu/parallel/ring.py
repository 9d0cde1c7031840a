"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference has no sequence models at all (SURVEY.md §5.7), but the
rebuild treats long-context as first-class: the mesh reserves a
``sequence`` axis (parallel/mesh.py) and this module supplies the two
standard SP attention strategies so sequence engines can shard tokens
without redesign:

- :func:`ring_attention` — K/V blocks rotate around the ring via
  ``ppermute`` (nearest-neighbor ICI traffic) while each device keeps its
  resident Q block; softmax is accumulated online (flash-attention style
  running max / denominator), so the full [S, S] score matrix never
  materializes.  Memory per device: O(S/n · S/n) per step.
- :func:`ulysses_attention` — ``all_to_all`` re-shards from
  sequence-sharded to head-sharded before a standard local attention,
  then back.  Cheaper at modest sequence lengths when heads ≥ devices.

Both are numerically equivalent to full attention (tests assert it) and
compose under ``jit``/``grad``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from predictionio_tpu.parallel.mesh import AXIS_SEQUENCE

__all__ = ["ring_attention", "ulysses_attention", "local_attention"]

_NEG = jnp.float32(-1e30)


def local_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False,
                    q_offset: int | jax.Array = 0,
                    k_offset: int | jax.Array = 0) -> jax.Array:
    """Plain softmax attention on one device. Shapes [B, S, H, D]."""
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = q_offset + jnp.arange(q.shape[1])
        kpos = k_offset + jnp.arange(k.shape[1])
        mask = qpos[:, None] >= kpos[None, :]
        scores = jnp.where(mask[None, None, :, :], scores, _NEG)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def ring_attention(
    q: jax.Array,  # [B, S, H, D] sharded on S over AXIS_SEQUENCE
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    causal: bool = False,
    axis: str = AXIS_SEQUENCE,
) -> jax.Array:
    """Exact attention over sequence-sharded Q/K/V with ring K/V rotation."""
    n = mesh.shape[axis]
    seq = q.shape[1]
    assert seq % n == 0, f"pad sequence ({seq}) to a multiple of {n}"
    s_local = seq // n
    scale = None  # applied inside local step

    def local(q_blk, k_blk, v_blk):
        # q_blk: [B, S/n, H, D]
        me = jax.lax.axis_index(axis)
        b, sl, h, d = q_blk.shape
        scale = d ** -0.5
        q_pos = me * sl + jnp.arange(sl)

        def step(t, carry):
            k_cur, v_cur, m, l, acc = carry
            src = (me - t) % n                      # owner of the visiting block
            k_pos = src * sl + jnp.arange(sl)
            s = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k_cur,
                           preferred_element_type=jnp.float32) * scale
            if causal:
                mask = q_pos[:, None] >= k_pos[None, :]
                s = jnp.where(mask[None, None, :, :], s, _NEG)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p, v_cur.astype(jnp.float32),
                preferred_element_type=jnp.float32)
            # Rotate K/V to the next device on the ring.
            perm = [(i, (i + 1) % n) for i in range(n)]
            k_nxt = jax.lax.ppermute(k_cur, axis, perm)
            v_nxt = jax.lax.ppermute(v_cur, axis, perm)
            return k_nxt, v_nxt, m_new, l_new, acc_new

        # pcast-to-varying: the accumulators become device-varying after
        # step 1; the loop carry must start with matching varying-axis
        # types.  Older jax has no varying-axis tracking (and no pcast) —
        # there the plain zeros ARE the right carry.
        _pcast = getattr(jax.lax, "pcast", None)
        if _pcast is None:
            def _pcast(x, _axis, to):
                return x
        m0 = _pcast(jnp.full((b, h, sl), _NEG, jnp.float32), axis, to='varying')
        l0 = _pcast(jnp.zeros((b, h, sl), jnp.float32), axis, to='varying')
        acc0 = _pcast(jnp.zeros((b, h, sl, d), jnp.float32), axis, to='varying')
        _, _, m, l, acc = jax.lax.fori_loop(
            0, n, step, (k_blk, v_blk, m0, l0, acc0))
        out = acc / jnp.maximum(l, 1e-30)[..., None]          # [B,H,S/n,D]
        return out.transpose(0, 2, 1, 3).astype(q_blk.dtype)  # [B,S/n,H,D]

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(None, axis), P(None, axis), P(None, axis)),
        out_specs=P(None, axis),
    )(q, k, v)


def ulysses_attention(
    q: jax.Array,  # [B, S, H, D] sharded on S over AXIS_SEQUENCE
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    causal: bool = False,
    axis: str = AXIS_SEQUENCE,
) -> jax.Array:
    """DeepSpeed-Ulysses-style SP: all_to_all seq→head reshard, local
    attention over the FULL sequence for H/n heads, all_to_all back."""
    n = mesh.shape[axis]
    seq, heads = q.shape[1], q.shape[2]
    assert seq % n == 0, f"pad sequence ({seq}) to a multiple of {n}"
    assert heads % n == 0, f"heads ({heads}) must divide over {n} devices"

    def local(q_blk, k_blk, v_blk):
        # [B, S/n, H, D] → exchange so each device gets all S for H/n heads.
        def seq_to_heads(x):
            # split_axis=2 (heads), concat_axis=1 (sequence)
            return jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                      tiled=True)

        def heads_to_seq(x):
            return jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                      tiled=True)

        qf, kf, vf = seq_to_heads(q_blk), seq_to_heads(k_blk), seq_to_heads(v_blk)
        out = local_attention(qf, kf, vf, causal=causal)
        return heads_to_seq(out)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(None, axis), P(None, axis), P(None, axis)),
        out_specs=P(None, axis),
    )(q, k, v)
