"""The kernels of the Mamba / sliding-window / shared-cache backbone
(:mod:`predictionio_tpu.models.sambay`), each with an XLA twin that
computes the same from the same arguments (the CPU path, and what the
tests hold the kernels to).

Both work on TILES: up to ``tq`` consecutive new events of ONE user,
tiles of a user back to back and in order.

``paged_attention``  softmax attention of a tile's query rows over the
    PAGES a list names (``pages``: a list per tile, handed over by scalar
    prefetch and fetched from the pool by the kernel's own DMA, ``pb``
    whole pages a step, double-buffered), dense over the positions a row
    may see: not after its own and, with ``window``, fewer than ``window``
    before it.  A page's row holds every kv-head pair's keys and then
    every pair's values, so ONE fetch serves all pairs; the kernel walks
    the pairs over the fetched pages.  A row's query is zero outside the
    half of the pair's keys it scores against, which is how the two score
    matrices of a differential pair come out of one product; the rows'
    softmaxes are separate and the caller takes their difference.  The
    window layers call it with their users' short page lists
    (``sambay_window_attention``), the shared cache's eight readers with
    whole histories (``sambay_shared_attention``).
``selective_scan``  the Mamba-1 recurrence ``h_t = exp(Delta_t A) h_{t-1}
    + (Delta_t x_t) B_t``, ``y_t = h_t C_t + D x_t``, an event at a time
    on a float32 ``[N, E]`` state held in VMEM across a user's tiles: read
    from the user's slot at the user's first tile, written to the slot the
    plan names after each (the state array aliased in place).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from predictionio_tpu.ops.pallas_kernels import pallas_supported

__all__ = ["paged_attention", "selective_scan", "PAGE_BITS", "PAGE_MASK",
           "SCAN_BLOCK"]

_NEG = -1e30
# ``pages`` entries: pool page << PAGE_BITS | the page's index in its
# user's history.
PAGE_BITS = 10
PAGE_MASK = (1 << PAGE_BITS) - 1
# Channels of the scan's state a grid step works on.
SCAN_BLOCK = 512


# -- paged attention ---------------------------------------------------------

def _row_mask(qpos, logical, *, page: int, window: int):
    """[rows, page] bool: the events of page ``logical`` (its index in the
    user's history) each query row sees.  ``qpos`` [rows, 1], -1 = a
    padding row."""
    at = logical * page + jax.lax.broadcasted_iota(
        jnp.int32, (qpos.shape[0], page), 1)
    ok = (at <= qpos) & (qpos >= 0)
    if window:
        ok = ok & (qpos - at < window)
    return ok


def _attn_kernel(cnt_ref, pages_ref, q_ref, qpos_ref, pool_ref, o_ref, buf,
                 sem, m_ref, l_ref, acc_ref, *, pairs: int, pb: int,
                 u_max: int, page: int, window: int):
    i = pl.program_id(0)
    steps = (cnt_ref[i] + pb - 1) // pb
    base = i * u_max
    pw = q_ref.shape[-1]

    def copies(step, slot):
        out = []
        for c in range(pb):
            row = pl.multiple_of(
                (pages_ref[base + step * pb + c] >> PAGE_BITS) * page, page)
            out.append(pltpu.make_async_copy(
                pool_ref.at[pl.ds(row, page)], buf.at[slot, c],
                sem.at[slot]))
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
        qpos = qpos_ref[0]
        ok = jnp.concatenate([
            _row_mask(qpos, pages_ref[base + step * pb + c] & PAGE_MASK,
                      page=page, window=window)
            for c in range(pb)], axis=1)                  # [rows, pb*page]
        for g in range(pairs):
            k = buf[slot, :, :, g * pw:(g + 1) * pw].reshape(pb * page, pw)
            v = buf[slot, :, :, (pairs + g) * pw:(pairs + g + 1) * pw
                    ].reshape(pb * page, pw)
            s = jax.lax.dot_general(
                q_ref[0, g], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            s = jnp.where(ok, s, _NEG)
            m_new = jnp.maximum(m_ref[g], jnp.max(s, axis=1, keepdims=True))
            p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
            scale = jnp.exp(m_ref[g] - m_new)
            l_ref[g] = l_ref[g] * scale + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[g] = acc_ref[g] * scale + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_ref[g] = m_new
        return carry

    jax.lax.fori_loop(0, steps, body, 0)
    o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def _attention_pallas(q, qpos, cnt, pages, pool, *, page: int, window: int,
                      pb: int, name: str, interpret: bool):
    nt, pairs, rows, pw = q.shape
    u_max = pages.shape[-1]
    kernel = functools.partial(_attn_kernel, pairs=pairs, pb=pb, u_max=u_max,
                               page=page, window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(nt,),
        in_specs=[
            pl.BlockSpec((1, pairs, rows, pw), lambda i, *_: (i, 0, 0, 0)),
            pl.BlockSpec((1, rows, 1), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, pairs, rows, pw),
                               lambda i, *_: (i, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, pb, page, pool.shape[1]), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((pairs, rows, 1), jnp.float32),
            pltpu.VMEM((pairs, rows, 1), jnp.float32),
            pltpu.VMEM((pairs, rows, pw), jnp.float32),
        ])
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nt, pairs, rows, pw), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 << 20),
        name=name, interpret=interpret,
    )(cnt.reshape(-1), pages.reshape(-1), q, qpos[..., None], pool)


def _attention_xla(q, qpos, cnt, pages, pool, *, page: int, window: int):
    nt, pairs, rows, pw = q.shape
    u_max = pages.shape[-1]
    logical = pages & PAGE_MASK
    listed = jnp.arange(u_max)[None] < cnt[:, None]
    ok = jax.vmap(jax.vmap(
        lambda qp, lp: _row_mask(qp, lp, page=page, window=window),
        in_axes=(None, 0)))(qpos[..., None], logical)  # [nt, u, rows, page]
    ok = ok & listed[:, :, None, None]
    ok = jnp.transpose(ok, (0, 2, 1, 3)).reshape(nt, 1, rows, u_max * page)
    paged = pool.reshape(-1, page, 2, pairs, pw)[pages >> PAGE_BITS]
    paged = paged.reshape(nt, u_max * page, 2, pairs, pw)
    s = jnp.einsum("ngrd,nsgd->ngrs", q, paged[:, :, 0],
                   preferred_element_type=jnp.float32)
    s = jnp.where(ok, s, _NEG)
    p = jnp.where(ok, jnp.exp(s - jnp.max(s, -1, keepdims=True)), 0.0)
    o = jnp.einsum("ngrs,nsgd->ngrd", p.astype(pool.dtype), paged[:, :, 1],
                   preferred_element_type=jnp.float32)
    return o / jnp.maximum(jnp.sum(p, -1, keepdims=True), 1e-30)


def paged_attention(q, qpos, cnt, pages, pool, *, page: int, window: int = 0,
                    pb: int = 4, name: str = "sambay_shared_attention",
                    use_pallas=None):
    """``q`` [tiles, pairs, rows, pw] (scaled; a row is zero outside the
    half of the pair's keys it scores), ``qpos`` [tiles, rows] int32 the
    rows' positions (-1: a padding row, which attends to nothing and comes
    back zero), ``cnt`` [tiles] pages listed, ``pages`` [tiles, u_max] (a
    multiple of ``pb``; entries past ``cnt`` name the scrap page), ``pool``
    [pages * page, 2 * pairs * pw]: an event a row, its keys by pair and
    then its values by pair along the lanes.  ``window``: a row sees the
    events fewer than this many before it (0: all).  -> [tiles, pairs,
    rows, pw] float32, each row's own softmax over what it sees."""
    if use_pallas is None:
        use_pallas = pallas_supported()
    if use_pallas:
        return _attention_pallas(q, qpos, cnt, pages, pool, page=page,
                                 window=window, pb=pb, name=name,
                                 interpret=not pallas_supported())
    return _attention_xla(q, qpos, cnt, pages, pool, page=page,
                          window=window)


# -- the selective scan ------------------------------------------------------

def _scan_tile(x, delta, bt, ct, a, d, h, at=0):
    """Up to a tile's events in order on the state ``h`` [N, eb]: ``x``,
    ``delta`` [n, eb] (a padding row has ``delta`` 0, which leaves the
    state as it is), ``bt``, ``ct`` [N, tq] with these events' columns
    from ``at`` on, ``a`` [N, eb], ``d`` [1, eb] -> (y [n, eb], the state
    after them)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, bt.shape, 1)
    rows = []
    for t in range(x.shape[0]):
        dt, xt = delta[t:t + 1], x[t:t + 1]
        # Column ``at + t`` by a masked sum: lanes cannot be sliced at a
        # running offset, and tq is at most a vreg's lanes.
        b = jnp.sum(jnp.where(lane == at + t, bt, 0.0), axis=1,
                    keepdims=True)
        c = jnp.sum(jnp.where(lane == at + t, ct, 0.0), axis=1,
                    keepdims=True)
        h = jnp.exp(dt * a) * h + (dt * xt) * b
        rows.append(jnp.sum(h * c, axis=0, keepdims=True) + d * xt)
    return jnp.concatenate(rows, axis=0), h


def _scan_kernel(first_ref, cnt_ref, rd_ref, wr_ref, x_ref, delta_ref,
                 bt_ref, ct_ref, a_ref, d_ref, s_in_ref, y_ref, s_out_ref,
                 s_scr, *, tq: int):
    del rd_ref, wr_ref                      # the index maps read them
    i, b = pl.program_id(0), pl.program_id(1)

    @pl.when(first_ref[i] == 1)
    def _():
        s_scr[b] = s_in_ref[0]

    @pl.when(cnt_ref[i] > 0)
    def _():
        a, d = a_ref[...], d_ref[...]

        def group(g, h):                    # 8 events: a sublane tile
            at = pl.multiple_of(g * 8, 8)
            y, h = _scan_tile(
                x_ref[0, pl.ds(at, 8), :], delta_ref[0, pl.ds(at, 8), :],
                bt_ref[0], ct_ref[0], a, d, h, at)
            y_ref[0, pl.ds(at, 8), :] = y
            return h

        s_scr[b] = jax.lax.fori_loop(0, tq // 8, group, s_scr[b])

    s_out_ref[0] = s_scr[b]


def _scan_pallas(x, delta, bt, ct, a, d, state, first, cnt, rd, wr, *,
                 eb: int, interpret: bool):
    nt, tq, e = x.shape
    n = a.shape[0]
    nb = e // eb
    rows = pl.BlockSpec((1, tq, eb), lambda i, b, *_: (i, 0, b))
    cols = pl.BlockSpec((1, n, tq), lambda i, b, *_: (i, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(nt, nb),
        in_specs=[
            rows, rows, cols, cols,
            pl.BlockSpec((n, eb), lambda i, b, *_: (0, b)),
            pl.BlockSpec((1, eb), lambda i, b, *_: (0, b)),
            pl.BlockSpec((1, n, eb),
                         lambda i, b, f, c, rd, wr: (rd[i], 0, b)),
        ],
        out_specs=[
            rows,
            pl.BlockSpec((1, n, eb),
                         lambda i, b, f, c, rd, wr: (wr[i], 0, b)),
        ],
        scratch_shapes=[pltpu.VMEM((nb, n, eb), jnp.float32)])
    y, state = pl.pallas_call(
        functools.partial(_scan_kernel, tq=tq), grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(x.shape, jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands: 4 prefetched, x, delta, bt, ct, a, d, state
        input_output_aliases={10: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="sambay_selective_scan", interpret=interpret,
    )(first, cnt, rd, wr, x, delta, bt, ct, a, d, state)
    return y, state


def _scan_xla(x, delta, bt, ct, a, d, state, first, cnt, rd, wr):
    del cnt                                 # a padding row has delta 0

    def event(h, row):
        xt, dt, b, c = row
        h = jnp.exp(dt[None, :] * a) * h + (dt * xt)[None, :] * b[:, None]
        return h, jnp.sum(h * c[:, None], axis=0) + d[0] * xt

    def tile(carry, t):
        state, h = carry
        xt, dt, b, c, f, r, w = t
        h = jnp.where(f == 1, state[r], h)
        h, y = jax.lax.scan(event, h, (xt, dt, b.T, c.T))
        return (state.at[w].set(h), h), y

    (state, _), y = jax.lax.scan(
        tile, (state, jnp.zeros(state.shape[1:], state.dtype)),
        (x, delta, bt, ct, first, rd, wr))
    return y, state


def selective_scan(x, delta, bt, ct, a, d, state, first, cnt, rd, wr, *,
                   use_pallas=None) -> Tuple[jax.Array, jax.Array]:
    """``x``, ``delta`` [tiles, tq, E] float32 (``delta`` 0 on a tile's
    rows past its count); ``bt``, ``ct`` [tiles, N, tq] (an event a
    column); ``a`` [N, E] = -exp(A_log); ``d`` [1, E]; ``state`` [slots, N,
    E] float32 (donated to the result); per tile: ``first`` (1 at a user's
    first tile: the state is read from slot ``rd``), ``cnt`` real events,
    ``wr`` the slot the state after the tile is written to (a user's tiles
    name one slot; a padding tile reads the zero slot and writes the scrap
    slot).  Returns (y [tiles, tq, E] float32, the state array)."""
    if use_pallas is None:
        use_pallas = pallas_supported()
    e, tq = x.shape[2], x.shape[1]
    if use_pallas and tq % 8 == 0:
        eb = SCAN_BLOCK if e % SCAN_BLOCK == 0 else e
        return _scan_pallas(x, delta, bt, ct, a, d, state, first, cnt, rd,
                            wr, eb=eb, interpret=not pallas_supported())
    return _scan_xla(x, delta, bt, ct, a, d, state, first, cnt, rd, wr)
