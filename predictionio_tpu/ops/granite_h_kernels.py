"""The kernel of the Mamba-2 / no-position attention backbone
(:mod:`predictionio_tpu.models.granite_h`), with an XLA twin that
computes the same from the same arguments (the CPU path, and what the
tests hold the kernel to).  The backbone's attention layers run
:func:`predictionio_tpu.ops.sambay_kernels.paged_attention`.

``ssd_update``  the Mamba-2 (SSD) recurrence ``S_t = exp(dt_t A_h)
    S_{t-1} + dt_t x_t[h] (x) B_t``, ``y_t[h] = S_t C_t`` (the caller adds
    the skip term ``D_h x_t[h]``) on a
    float32 ``[heads, P, N]`` state a user, ``N`` along the lanes.  It
    works on TILES: up to ``tq`` consecutive new events of ONE user, tiles
    of a user back to back and in order.  Inside a tile the matmul form:
    with ``cs_t`` the running sum of the log-decays ``dt A_h`` up to and
    with event ``t`` (a per-event, per-head number, where lightning
    attention has ``rate * lag``),

        y_t[h]   = sum_{s<=t} (C_t . B_s) exp(cs_t - cs_s) dt_s x_s[h]
                   + exp(cs_t) S_in C_t
        S_out    = exp(cs_last) S_in
                   + sum_s exp(cs_last - cs_s) (dt_s x_s[h]) (x) B_s

    and since ``B`` and ``C`` are every head's (one group), ``C B^T`` is
    ONE [tq, tq] product a tile, the carried state's read-out ONE [tq, N]
    x [N, heads x P] product and the state's update ONE [heads x P, tq] x
    [tq, N] product a head block; only the masked product over a tile's
    own events is a head's ([tq, tq] x [tq, P]).  The rows come and go
    as the caller holds them, ``heads x P`` channels along the lanes:
    nothing outside the kernel is viewed ``[..., heads, P]``.  The
    numbers a (tile, event, head) (``dt`` and ``cs``) are made by XLA
    beside the call, and a head's are spread over its ``P`` lanes HERE,
    for all of a block's heads at once (:func:`_spread`: a product with
    a 0/1 matrix in full float32, exact, on the MXU).
    Across a user's tiles the state is carried in VMEM: read from the
    user's slot at the user's first tile, written to the slot the plan
    names after each (the state array aliased to the result).  A padding
    row has ``dt`` 0: it decays nothing and adds nothing.

Precision: ``B``, ``C``, ``dt x`` and the masked ``C B^T`` enter the
products as bfloat16 (matmul inputs); the state stays float32: it enters
its read-out, and ``dt x`` the state's update, as TWO bfloat16 terms (the
value and what rounding it left), 16 bits of mantissa; the log-decays,
their running sums, every exponential and every accumulation are float32.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from predictionio_tpu.ops.pallas_kernels import pallas_supported

__all__ = ["ssd_update", "HEAD_BLOCK"]

# Heads of the state a grid step works on.
HEAD_BLOCK = 32
_NT = (((1,), (1,)), ((), ()))      # a [m, k] x [n, k]^T product
_TN = (((0,), (0,)), ((), ()))      # a [k, m]^T x [k, n] product


def _two_terms(x):
    """A float32 array as two bfloat16 terms whose sum holds 16 bits of
    its mantissa."""
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def _read_out(bb, cc, s2):
    """What a tile reads for all of a block's heads at once, since ``B``
    and ``C`` are every head's: (``C B^T`` [tq, tq], the carried state's
    read-out ``C S^T`` [tq, heads * P]).  ``s2`` [heads * P, N] float32
    enters as two bfloat16 terms."""
    f32 = jnp.float32
    g = jax.lax.dot_general(cc, bb, _NT, preferred_element_type=f32)
    s_hi, s_lo = _two_terms(s2)
    return g, (jax.lax.dot_general(cc, s_hi, _NT, preferred_element_type=f32)
               + jax.lax.dot_general(cc, s_lo, _NT,
                                     preferred_element_type=f32))


def _spread(v, hp):
    """A number a (row, head) [m, heads] over the head's ``hp`` lanes ->
    [m, heads * hp]: a product with a 0/1 matrix in full float32 (exact:
    one term a sum), on the MXU.  A lane broadcast a head and number
    costs the kernel more than it has to spare beside its DMA."""
    heads = v.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (heads, heads * hp), 1) \
        - hp * jax.lax.broadcasted_iota(jnp.int32, (heads, heads * hp), 0)
    ones = ((lane >= 0) & (lane < hp)).astype(jnp.float32)
    return jnp.dot(v, ones, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _block(hp, x, dt, cs, before):
    """For all of a block's heads at once: (``dt x`` [tq, heads * P],
    the carried state's part of y, ``w dt x``: the events' increments as
    the tile's last event sees them)."""
    tq = x.shape[0]
    sp = _spread(jnp.concatenate(
        [dt, jnp.exp(cs), jnp.exp(cs[-1:, :] - cs)], axis=0), hp)
    xd = sp[:tq] * x
    return xd, sp[tq:2 * tq] * before, sp[2 * tq:] * xd


def _head(h, hp, xd, cs, cs_t, g, tri):
    """Head ``h`` of a tile: the masked product over the tile's own
    events [tq, P]."""
    lanes = slice(h * hp, (h + 1) * hp)
    col, row = cs[:, h:h + 1], cs_t[h:h + 1, :]
    decay = jnp.where(tri, jnp.exp(jnp.minimum(col - row, 0.0)), 0.0)
    return jnp.dot((g * decay).astype(jnp.bfloat16),
                   xd[:, lanes].astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)


def _update(xw, bb):
    """The state's update ``(w dt x)^T B`` [heads * P, N] for all of a
    block's heads at once; ``xw`` [tq, heads * P] float32 enters as two
    bfloat16 terms."""
    w_hi, w_lo = _two_terms(xw)
    return (jax.lax.dot_general(w_hi, bb, _TN,
                                preferred_element_type=jnp.float32)
            + jax.lax.dot_general(w_lo, bb, _TN,
                                  preferred_element_type=jnp.float32))


def _triangle(tq: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (tq, tq), 1)
            <= jax.lax.broadcasted_iota(jnp.int32, (tq, tq), 0))


def _ssd_kernel(first_ref, cnt_ref, rd_ref, wr_ref, dl_ref, x_ref, dt_ref,
                cs_ref, cst_ref, b_ref, c_ref, s_in_ref, y_ref, s_out_ref,
                s_scr, *, hb: int, heads: int):
    del rd_ref, wr_ref                      # the index maps read them
    blk, i = pl.program_id(0), pl.program_id(1)
    hp, n = s_scr.shape[1], s_scr.shape[2]

    @pl.when(first_ref[i] == 1)
    def _():
        s_scr[...] = s_in_ref[0]

    @pl.when(cnt_ref[i] > 0)
    def _():
        x, dt, cs, cs_t = x_ref[0], dt_ref[0, 0], cs_ref[0, 0], cst_ref[0, 0]
        bb = b_ref[0]
        g, before = _read_out(bb, c_ref[0], s_scr[...].reshape(hb * hp, n))
        tri = _triangle(x.shape[0])
        xd, carried, xw = _block(hp, x, dt, cs, before)
        for h in range(hb):
            lanes = slice(h * hp, (h + 1) * hp)
            y_ref[0, :, lanes] = _head(h, hp, xd, cs, cs_t, g, tri) \
                + carried[:, lanes]
        upd = _update(xw, bb)
        for h in range(hb):
            s_scr[h] = dl_ref[i * heads + blk * hb + h] * s_scr[h] \
                + upd[h * hp:(h + 1) * hp]

    s_out_ref[0] = s_scr[...]


def _ssd_pallas(x, dt, cs, dl, b, c, state, first, cnt, rd, wr, *, hb: int,
                interpret: bool):
    nt, tq, _ = x.shape
    heads, hp, n = state.shape[1:]
    nb = heads // hb

    def by_block(v):
        """A number a (tile, event, head) by head block, the events along
        the sublanes: a head's column is a static slice of it."""
        return jnp.transpose(v.reshape(nt, tq, nb, hb), (0, 2, 1, 3))
    cs_b = by_block(cs)
    rows = pl.BlockSpec((1, tq, hb * hp), lambda g, i, *_: (i, 0, g))
    cols = pl.BlockSpec((1, 1, tq, hb), lambda g, i, *_: (i, g, 0, 0))
    shared = pl.BlockSpec((1, tq, n), lambda g, i, *_: (i, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5, grid=(nb, nt),
        in_specs=[
            rows, cols, cols,
            pl.BlockSpec((1, 1, hb, tq), lambda g, i, *_: (i, g, 0, 0)),
            shared, shared,
            pl.BlockSpec((1, hb, hp, n),
                         lambda g, i, f, k, rd, wr, dl: (rd[i], g, 0, 0)),
        ],
        out_specs=[
            rows,
            pl.BlockSpec((1, hb, hp, n),
                         lambda g, i, f, k, rd, wr, dl: (wr[i], g, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((hb, hp, n), jnp.float32)])
    y, state = pl.pallas_call(
        functools.partial(_ssd_kernel, hb=hb, heads=heads),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(x.shape, jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands: 5 prefetched, x, dt, cs, cs_t, b, c, state
        input_output_aliases={11: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        name="granite_h_ssd_update", interpret=interpret,
    )(first, cnt, rd, wr, dl.reshape(-1), x, by_block(dt), cs_b,
      jnp.swapaxes(cs_b, 2, 3), b, c, state)
    return y, state


def _ssd_xla(x, dt, cs, dl, b, c, state, first, cnt, rd, wr):
    del cnt                                 # a padding row has dt 0
    heads, hp, n = state.shape[1:]
    tri = _triangle(x.shape[1])

    def tile(carry, t):
        state, s = carry
        xt, dtt, cst, dlt, bb, cc, f, r, w = t
        s = jnp.where(f == 1, state[r], s)
        g, before = _read_out(bb, cc, s.reshape(heads * hp, n))
        xd, carried, xw = _block(hp, xt, dtt, cst, before)
        y = [_head(h, hp, xd, cst, cst.T, g, tri)
             + carried[:, h * hp:(h + 1) * hp] for h in range(heads)]
        upd = _update(xw, bb)
        s = dlt[:, None, None] * s + upd.reshape(heads, hp, n)
        return (state.at[w].set(s), s), jnp.concatenate(y, axis=1)

    (state, _), y = jax.lax.scan(
        tile, (state, jnp.zeros(state.shape[1:], state.dtype)),
        (x, dt, cs, dl, b, c, first, rd, wr))
    return y, state


def ssd_update(x, dt, b, c, a, state, first, cnt, rd, wr, *,
               hb: int = HEAD_BLOCK, use_pallas=None
               ) -> Tuple[jax.Array, jax.Array]:
    """``x`` [tiles, tq, heads * P] float32, head ``h``'s channels lanes
    ``h P ... (h + 1) P``; ``dt`` [tiles, tq, heads] float32 (0 on a
    tile's rows past its count), a number a head: the kernel spreads it
    (and the running sums made from it here) over the head's lanes, the
    caller never does; ``b``, ``c`` [tiles, tq, N]; ``a`` [heads] =
    -exp(A_log); ``state`` [slots, heads, P, N] float32 (donated to the
    result); per tile: ``first`` (1 at a user's first tile: the state is
    read from slot ``rd``), ``cnt`` real events, ``wr`` the slot the
    state after the tile is written to (a user's tiles name one slot; a
    padding tile names the slots of the tile before it, with ``first``
    0, and so moves nothing).  Returns (y [tiles, tq, heads * P] float32
    = ``S_t C_t``: the skip term ``D_h x_t[h]`` is the caller's, on the
    event's own row; the state array)."""
    if use_pallas is None:
        use_pallas = pallas_supported()
    heads = state.shape[1]
    # The running sums of the log-decays, a number a (tile, event, head),
    # are made here, by XLA.
    cs = jnp.cumsum(dt * a, axis=1)                 # [tiles, tq, heads]
    args = (x, dt, cs, jnp.exp(cs[:, -1]), b.astype(jnp.bfloat16),
            c.astype(jnp.bfloat16), state, first, cnt, rd, wr)
    if use_pallas:
        return _ssd_pallas(*args, hb=hb if heads % hb == 0 else heads,
                           interpret=not pallas_supported())
    return _ssd_xla(*args)
