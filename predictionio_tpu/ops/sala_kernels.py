"""The two kernels of the block-selected / lightning backbone
(:mod:`predictionio_tpu.models.sala`), each with an XLA twin that
computes the same from the same arguments (the CPU path, and what the
tests hold the kernels to).

Both work on TILES: up to ``tq`` consecutive new events of ONE user,
tiles of a user back to back and in order.  ``tq`` is the program's
choice (8 for short turns, 64 for prefill chunks).

``sala_sparse_attention``  causal softmax attention of a tile's queries
    over the PAGES its users' selection named (``pages``: a list per
    (tile, kv group), handed over by scalar prefetch and fetched from the
    pool by the kernel's own DMA, ``pb`` pages a step, double-buffered),
    so a selected page of a (user, group) is read once for all of the
    tile's queries.  Inside a page a query keeps the blocks it selected
    itself (or every block, on the dense path) at positions not after
    its own.
``sala_lightning``  the decayed linear-attention state: inside a tile by
    masked products with the decay, across tiles of a user through ``S``
    (VMEM), read from the user's slot at the user's first tile and
    written to the slot the plan names after each.
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from predictionio_tpu.ops.pallas_kernels import pallas_supported

__all__ = ["sparse_attention", "lightning", "POS_LANE", "DENSE_LANE",
           "PAGE_BITS"]

_NEG = -1e30
# ``meta`` lanes: [0, topk) the query's selected block ids (-1 = none),
# its position (-1 = a padding row) and whether it is on the dense path.
POS_LANE, DENSE_LANE = 126, 127
# ``pages`` entries: pool page << PAGE_BITS | the page's index in its
# user's history.
PAGE_BITS = 10


# -- selected-block attention ------------------------------------------------

def _page_mask(meta, logical, *, page: int, block: int, topk: int):
    """[tq, page] int32 (1 or 0): which rows of page ``logical`` (its
    index in the user's history) each query of the tile attends to."""
    pos = meta[:, POS_LANE:POS_LANE + 1]
    dense = meta[:, DENSE_LANE:DENSE_LANE + 1] > 0
    lane = jax.lax.broadcasted_iota(jnp.int32, (meta.shape[0], page), 1)
    sel_lane = jax.lax.broadcasted_iota(jnp.int32, meta.shape, 1)
    member = jnp.zeros((meta.shape[0], page), jnp.int32)
    for b in range(page // block):
        hit = jnp.max(jnp.where((meta == logical * (page // block) + b)
                                & (sel_lane < topk), 1, 0),
                      axis=1, keepdims=True)
        member = jnp.where(lane // block == b, hit, member)
    return jnp.where((logical * page + lane <= pos)
                     & (dense | (member > 0)), 1, 0)


def _sparse_attn_kernel(cnt_ref, pages_ref, q_ref, meta_ref, pool_ref,
                        o_ref, buf, sem, m_ref, l_ref, acc_ref, *,
                        groups: int, heads: int, pb: int, u_max: int,
                        page: int, block: int, topk: int):
    g = pl.program_id(1)
    w = pl.program_id(0) * groups + g
    steps = (cnt_ref[w] + pb - 1) // pb
    base = w * u_max

    hd = buf.shape[-1]

    def copies(step, slot):
        out = []
        for c in range(pb):
            row = pl.multiple_of(
                (pages_ref[base + step * pb + c] >> PAGE_BITS) * page, page)
            for kv in range(2):          # keys, then values, of group g
                lanes = pl.multiple_of((kv * groups + g) * hd, hd)
                out.append(pltpu.make_async_copy(
                    pool_ref.at[pl.ds(row, page), pl.ds(lanes, hd)],
                    buf.at[slot, c, kv], sem.at[slot]))
        return out

    m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(steps > 0)
    def _():
        for c in copies(0, 0):
            c.start()

    def body(step, carry):
        slot = step % 2

        @pl.when(step + 1 < steps)
        def _():
            for c in copies(step + 1, 1 - slot):
                c.start()

        for c in copies(step, slot):
            c.wait()
        meta = meta_ref[0, 0]
        ok = jnp.concatenate([
            _page_mask(meta, pages_ref[base + step * pb + c]
                       & ((1 << PAGE_BITS) - 1),
                       page=page, block=block, topk=topk)
            for c in range(pb)], axis=1)                  # [tq, pb*page]
        ok = jnp.concatenate([ok] * heads, axis=0) > 0    # head-major rows
        k = buf[slot, :, 0].reshape(pb * page, hd)
        v = buf[slot, :, 1].reshape(pb * page, hd)
        s = jax.lax.dot_general(q_ref[0, 0], k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(ok, s, _NEG)
        m_new = jnp.maximum(m_ref[...], jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        scale = jnp.exp(m_ref[...] - m_new)
        l_ref[...] = l_ref[...] * scale + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * scale + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        return carry

    jax.lax.fori_loop(0, steps, body, 0)
    o_ref[0, 0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def _sparse_attention_pallas(q, meta, cnt, pages, pool, *, page: int,
                             block: int, topk: int, pb: int,
                             interpret: bool):
    nt, groups, rows, hd = q.shape
    tq = meta.shape[2]
    u_max = pages.shape[-1]
    kernel = functools.partial(
        _sparse_attn_kernel, groups=groups, heads=rows // tq, pb=pb,
        u_max=u_max, page=page, block=block, topk=topk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(nt, groups),
        in_specs=[
            pl.BlockSpec((1, 1, rows, hd), lambda i, g, *_: (i, g, 0, 0)),
            pl.BlockSpec((1, 1, tq, 128), lambda i, g, *_: (i, g, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, 1, rows, hd),
                               lambda i, g, *_: (i, g, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, pb, 2, page, hd), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, hd), jnp.float32),
        ])
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nt, groups, rows, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        name="sala_sparse_attention", interpret=interpret,
    )(cnt.reshape(-1), pages.reshape(-1), q, meta, pool)


def _sparse_attention_xla(q, meta, cnt, pages, pool, *, page: int,
                          block: int, topk: int):
    nt, groups, rows, hd = q.shape
    tq, u_max = meta.shape[2], pages.shape[-1]
    heads = rows // tq
    logical = pages & ((1 << PAGE_BITS) - 1)
    listed = jnp.arange(u_max)[None, None] < cnt[..., None]
    mask = jax.vmap(jax.vmap(jax.vmap(
        lambda m, lp: _page_mask(m, lp, page=page, block=block, topk=topk),
        in_axes=(None, 0))))(meta, logical)        # [nt, g, u, tq, page]
    mask = (mask > 0) & listed[..., None, None]
    paged = pool.reshape(-1, page, 2, groups, hd)[pages >> PAGE_BITS]
    kv = jnp.stack([paged[:, g, :, :, :, g] for g in range(groups)], 1)
    k = kv[:, :, :, :, 0].reshape(nt, groups, u_max * page, hd)
    v = kv[:, :, :, :, 1].reshape(nt, groups, u_max * page, hd)
    ok = jnp.transpose(mask, (0, 1, 3, 2, 4)).reshape(
        nt, groups, tq, u_max * page)
    ok = jnp.tile(ok, (1, 1, heads, 1))
    s = jnp.einsum("ngrd,ngsd->ngrs", q, k,
                   preferred_element_type=jnp.float32)
    s = jnp.where(ok, s, _NEG)
    p = jnp.where(ok, jnp.exp(s - jnp.max(s, -1, keepdims=True)), 0.0)
    o = jnp.einsum("ngrs,ngsd->ngrd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o / jnp.maximum(jnp.sum(p, -1, keepdims=True), 1e-30)


def sparse_attention(q, meta, cnt, pages, pool, *, page: int, block: int,
                     topk: int, pb: int = 8, use_pallas=None):
    """``q`` [tiles, groups, heads * tq, hd] (rows head-major, scaled by
    ``1 / sqrt(hd)``), ``meta`` [tiles, groups, tq, 128] int32, ``cnt``
    [tiles, groups] pages listed, ``pages`` [tiles, groups, u_max] (a
    multiple of ``pb``; entries past ``cnt`` name the scrap page at an
    index no position reaches), ``pool`` [pages * page, 2 (k, v) * groups
    * hd]: an event a row, its keys by group and then its values by group
    along the lanes -> [tiles, groups, heads * tq, hd] float32."""
    if use_pallas is None:
        use_pallas = pallas_supported()
    if use_pallas:
        return _sparse_attention_pallas(
            q, meta, cnt, pages, pool, page=page, block=block, topk=topk,
            pb=pb, interpret=not pallas_supported())
    return _sparse_attention_xla(q, meta, cnt, pages, pool, page=page,
                                 block=block, topk=topk)


# -- lightning attention -----------------------------------------------------

def _lightning_tile(q, k, v, s, rate, n, scale):
    """One head's tile: (outputs [tq, hd], the state after the tile's
    first ``n`` events).  ``q``, ``k``, ``v`` [tq, hd], ``s`` [hd, hd]
    float32, ``rate`` = -log(lambda) (a [1, 1] array)."""
    tq = q.shape[0]
    hi = jax.lax.Precision.HIGHEST
    i = jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0)
    ii = jax.lax.broadcasted_iota(jnp.int32, (tq, tq), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (tq, tq), 1)
    lag = jnp.maximum(ii - jj, 0).astype(jnp.float32)
    decay = jnp.where(jj <= ii, jnp.exp(-rate * lag), 0.0)
    qk = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    inside = jnp.dot((qk * decay).astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    before = jnp.dot(q.astype(jnp.float32), s, precision=hi,
                     preferred_element_type=jnp.float32)
    o = scale * (jnp.exp(-rate * (i + 1).astype(jnp.float32)) * before
                 + inside)
    left = jnp.maximum(n - 1 - i, 0).astype(jnp.float32)
    kw = jnp.where(i < n, jnp.exp(-rate * left), 0.0) * k.astype(jnp.float32)
    s_new = jnp.exp(-rate * n.astype(jnp.float32)) * s + jax.lax.dot_general(
        kw, v.astype(jnp.float32), (((0,), (0,)), ((), ())), precision=hi,
        preferred_element_type=jnp.float32)
    return o, s_new


def _lightning_kernel(first_ref, cnt_ref, rd_ref, wr_ref, rate_ref, q_ref,
                      k_ref, v_ref, s_in_ref, o_ref, s_out_ref, s_scr, *,
                      hb: int, scale: float):
    del rd_ref, wr_ref                      # the index maps read them
    i = pl.program_id(1)

    @pl.when(first_ref[i] == 1)
    def _():
        s_scr[...] = s_in_ref[0]

    n = cnt_ref[i]
    for h in range(hb):
        o, s_new = _lightning_tile(q_ref[0, h], k_ref[0, h], v_ref[0, h],
                                   s_scr[h], rate_ref[h][:, :1], n, scale)
        o_ref[0, h] = o
        s_scr[h] = s_new
    s_out_ref[0] = s_scr[...]


def _lightning_pallas(q, k, v, state, rate, first, cnt, rd, wr, *, scale,
                      hb: int, interpret: bool):
    nt, heads, tq, hd = q.shape
    rate_b = jnp.broadcast_to(rate.astype(jnp.float32)[:, None, None],
                              (heads, 1, 128))
    tile = pl.BlockSpec((1, hb, tq, hd), lambda b, i, *_: (i, b, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(heads // hb, nt),
        in_specs=[
            pl.BlockSpec((hb, 1, 128), lambda b, i, *_: (b, 0, 0)),
            tile, tile, tile,
            pl.BlockSpec((1, hb, hd, hd),
                         lambda b, i, f, c, rd, wr: (rd[i], b, 0, 0)),
        ],
        out_specs=[
            tile,
            pl.BlockSpec((1, hb, hd, hd),
                         lambda b, i, f, c, rd, wr: (wr[i], b, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((hb, hd, hd), jnp.float32)])
    o, state = pl.pallas_call(
        functools.partial(_lightning_kernel, hb=hb, scale=scale),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(q.shape, jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands: 4 prefetched, rate, q, k, v, state
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="sala_lightning", interpret=interpret,
    )(first, cnt, rd, wr, rate_b, q, k, v, state)
    return o, state


def _lightning_xla(q, k, v, state, rate, first, cnt, rd, wr, *, scale):
    def step(carry, x):
        state, s = carry
        qt, kt, vt, f, n, r, w = x
        s = jnp.where(f == 1, state[r], s)
        o, s = jax.vmap(
            lambda a, b, c, d, e: _lightning_tile(a, b, c, d, e[None, None],
                                                  n, scale)
        )(qt, kt, vt, s, rate.astype(jnp.float32))
        return (state.at[w].set(s), s), o

    (state, _), o = jax.lax.scan(
        step, (state, jnp.zeros(state.shape[1:], state.dtype)),
        (q, k, v, first, cnt, rd, wr))
    return o, state


def lightning(q, k, v, state, rate, first, cnt, rd, wr, *, hb: int = 8,
              use_pallas=None) -> Tuple[jax.Array, jax.Array]:
    """``q``, ``k``, ``v`` [tiles, heads, tq, hd]; ``state`` [slots,
    heads, hd, hd] float32 (donated to the result); ``rate`` [heads] =
    -log(lambda); per tile: ``first`` (1 at a user's first tile: the state
    is read from slot ``rd``), ``cnt`` real events, ``wr`` the slot the
    state after the tile is written to (a user's tiles name one slot; a
    padding tile reads the zero slot and writes the scrap slot).  Returns
    (outputs [tiles, heads, tq, hd] float32, the state array)."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    if use_pallas is None:
        use_pallas = pallas_supported()
    if use_pallas:
        return _lightning_pallas(q, k, v, state, rate, first, cnt, rd, wr,
                                 scale=scale, hb=hb,
                                 interpret=not pallas_supported())
    return _lightning_xla(q, k, v, state, rate, first, cnt, rd, wr,
                          scale=scale)
