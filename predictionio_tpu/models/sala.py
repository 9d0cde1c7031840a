"""A sequence backbone of block-selected sparse attention beside
lightning linear attention (the MiniCPM-SALA layer pattern), for
next-item prediction over a user's event history, served from per-user
state of three kinds.

**Equations** (``d`` hidden size; ``RMS_n(x; g) = x / sqrt(mean_n(x^2) +
eps) * g``; ``c = scale_depth / sqrt(L)``, ``L`` the PUBLISHED depth,
also where fewer layers are held):

* Block: ``h = x + c Mixer(RMS(x))``, ``y = h + c MLP(RMS(h))``,
  ``MLP(u) = W_down(silu(W_gate u) * W_up u)``.  Input ``x_0 = scale_emb
  E[item]``.  After the last held layer one RMS, then ``logits = (h /
  (d / dim_model_base)) W_head^T``, the head untied.
* ``lightning-attn``, head ``h`` of ``lightning_nh``: ``q = rope(RMS_hd(
  W_q u))``, ``k = rope(RMS_hd(W_k u))``, ``v = W_v u``; ``S_t = lambda_h
  S_{t-1} + k_t^T v_t`` (hd x hd), ``o_t = (q_t / sqrt(hd)) S_t``; ``out
  = W_o(sigmoid(W_z u) * RMS_d(o))``.  ``lambda_h = exp(-s_h (1 - l /
  (L - 1) + 1e-5))``, ``s_h = 2^(-8 (h + 1) / H)``, ``l`` the PUBLISHED
  layer index (assumed: Lightning Attention-2's form).
* ``minicpm4`` (InfLLM-V2), query heads in ``num_key_value_heads``
  groups, no rotary: ``q = RMS_hd(W_q u)``, ``k = RMS_hd(W_k u)``, ``v =
  W_v u``; a query at position ``p``:

  - ``p + 1 <= dense_len``: causal softmax attention over all events;
  - else, per group: pooled keys ``K_j = mean(k[stride j : stride j +
    kernel_size])`` of every window that ends at or before ``p``;
    ``a_{h,j} = softmax_j(q_h . K_j / sqrt(hd))``; ``A_j = sum_{h in g}
    a_{h,j}``; block ``b`` (``block_size`` events) scores ``max(A_{r b -
    1} ... A_{r b + r - 1})``, ``r = block_size / stride``; the first
    ``init_blocks`` and the ``window_size / block_size`` blocks that end
    with the query's own are forced; the ``topk`` best blocks are the
    selection; causal softmax attention over their events at positions
    ``<= p``.
  - ``out = W_o(sigmoid(W_z u) * o)``.

  One departure from the published code, noted: the switch is taken per
  query by its own position, so a history read at once and the same
  history read turn by turn give one answer.

The plain equations are in
:mod:`predictionio_tpu.models.sala_reference`; this module computes the
same for a RAGGED batch of new events of several users against each
user's cached state (:func:`extend_step`): a float32 ``hd x hd`` matrix a
lightning head and layer in the user's FIXED slot, keys and values of
the sparse layers in the user's PAGES, and the pooled keys the selection
scores in INDEX rows that ride with the pages (a window that straddles a
page border belongs to the page it starts in, and is written when its
last event arrives).  The new events are cut into TILES of up to ``tq``
events of one user; both kernels (:mod:`predictionio_tpu.ops.sala_kernels`)
work a tile at a time.

Weights, keys, values, pooled keys and matmul inputs are bfloat16; the
residual stream, norms, softmax, selection scores, the recurrent state
and every accumulation are float32.  Where the equations split a product
the factors are columns of ONE matrix (``w_qkv``, ``w13``), in the order
the equations name them.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.models.lfm2 import _mm, rms, rope
from predictionio_tpu.obs import get_registry
from predictionio_tpu.ops import sala_kernels
from predictionio_tpu.ops.ragged import TurnPack

__all__ = ["SALAConfig", "init_params", "cast_for_serving", "extend_step",
           "SALAStep", "state_layout", "selection_counts"]

TOKEN_BUCKETS = (256, 1024)
READ_BUCKETS = (8, 64)
# Pages a user's device page table holds: 640 x 128 = 81,920 events.
TABLE_LEN = 640
LIGHTNING, SPARSE = "lightning-attn", "minicpm4"
_NEG = -1e30


def _pages_per_step(tq: int) -> int:
    """Pages the attention kernel fetches a step, for tiles of ``tq``."""
    return 8 if tq <= 16 else 4


@dataclasses.dataclass(frozen=True)
class SALAConfig:
    """Shape of the backbone.  ``mixer_types[i]`` is the kind of held
    layer ``i`` and ``layer_index[i]`` its PUBLISHED index (the decay of
    a lightning layer depends on it)."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    lightning_nh: int
    lightning_head_dim: int
    mixer_types: Tuple[str, ...]
    layer_index: Tuple[int, ...]
    published_layers: int
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    # InfLLM-V2's sparse_config (assumed: MiniCPM4's published values).
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    topk: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    dense_len: int = 8192

    def __post_init__(self):
        bad = set(self.mixer_types) - {LIGHTNING, SPARSE}
        if bad:
            raise ValueError(f"unknown mixer type(s) {sorted(bad)}")
        if len(self.mixer_types) != len(self.layer_index):
            raise ValueError("mixer_types and layer_index differ in length")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads do not divide into kv heads")
        if self.block_size % self.kernel_stride \
                or self.kernel_size > self.block_size:
            raise ValueError("a block is a whole number of strides and "
                             "holds a pooling window")
        if self.topk < self.init_blocks + self.window_size \
                // self.block_size:
            raise ValueError("topk is below the forced blocks")
        if self.topk > sala_kernels.POS_LANE:
            raise ValueError(f"topk over {sala_kernels.POS_LANE}")

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.published_layers)

    @property
    def head_divisor(self) -> float:
        return self.hidden_size / self.dim_model_base

    @property
    def n_lightning(self) -> int:
        return sum(t == LIGHTNING for t in self.mixer_types)

    @property
    def n_sparse(self) -> int:
        return len(self.mixer_types) - self.n_lightning

    @classmethod
    def from_published(cls, doc: Dict[str, Any],
                       held_layers: Optional[Sequence[int]] = None,
                       **sparse) -> "SALAConfig":
        """From the keys of the published ``config.json``; ``held_layers``
        picks published layer indices (all by default); ``sparse``: the
        ``sparse_config`` keys the published file carries."""
        types = list(doc["mixer_types"])
        held = list(range(len(types))) if held_layers is None \
            else [int(i) for i in held_layers]
        return cls(
            vocab_size=int(doc["vocab_size"]),
            hidden_size=int(doc["hidden_size"]),
            intermediate_size=int(doc["intermediate_size"]),
            num_attention_heads=int(doc["num_attention_heads"]),
            num_key_value_heads=int(doc["num_key_value_heads"]),
            head_dim=int(doc["head_dim"]),
            lightning_nh=int(doc["lightning_nh"]),
            lightning_head_dim=int(doc["lightning_head_dim"]),
            mixer_types=tuple(types[i] for i in held),
            layer_index=tuple(held), published_layers=len(types),
            scale_emb=float(doc["scale_emb"]),
            scale_depth=float(doc["scale_depth"]),
            dim_model_base=int(doc["dim_model_base"]),
            rope_theta=float(doc["rope_theta"]),
            rms_norm_eps=float(doc["rms_norm_eps"]),
            **{k: int(v) for k, v in sparse.items()})


# -- weights -----------------------------------------------------------------

def layer_shapes(cfg: SALAConfig, i: int) -> Dict[str, Tuple[int, ...]]:
    """Name -> shape of held layer ``i``'s weights."""
    d, f = cfg.hidden_size, cfg.intermediate_size
    out: Dict[str, Tuple[int, ...]] = {
        "op_norm": (d,), "ffn_norm": (d,), "w13": (d, 2 * f), "w2": (f, d)}
    if cfg.mixer_types[i] == LIGHTNING:
        w, hd = cfg.lightning_nh * cfg.lightning_head_dim, \
            cfg.lightning_head_dim
        out.update(w_qkv=(d, 3 * w), o_norm=(w,))
    else:
        w, hd = cfg.num_attention_heads * cfg.head_dim, cfg.head_dim
        out.update(w_qkv=(d, w + 2 * cfg.num_key_value_heads * hd))
    out.update(q_norm=(hd,), k_norm=(hd,), w_z=(d, w), w_o=(w, d))
    return out


def init_params(cfg: SALAConfig, key: jax.Array, dtype=jnp.bfloat16
                ) -> Dict[str, Any]:
    """Seeded weights: products normal / sqrt(fan-in) in ``dtype``, norm
    weights 1 + 0.1 normal float32."""
    def draw(k, name, shape):
        x = jax.random.normal(k, shape, jnp.float32)
        if name.endswith("_norm"):
            return 1.0 + 0.1 * x
        return (x / math.sqrt(shape[0])).astype(dtype)

    d = cfg.hidden_size
    ke, kh, kn = jax.random.split(jax.random.fold_in(key, 1 << 20), 3)
    return {
        "embed": (jax.random.normal(ke, (cfg.vocab_size, d), jnp.float32)
                  / math.sqrt(d)).astype(dtype),
        "head": draw(kh, "head", (cfg.vocab_size, d)),
        "final_norm": draw(kn, "final_norm", (d,)),
        "layers": [{name: draw(jax.random.fold_in(
            jax.random.fold_in(key, i), j), name, shape)
            for j, (name, shape) in enumerate(sorted(
                layer_shapes(cfg, i).items()))}
            for i in range(len(cfg.mixer_types))],
    }


def cast_for_serving(params: Dict[str, Any]) -> Dict[str, Any]:
    """The serving precision: products bfloat16, norms float32."""
    def cast(name, x):
        return jnp.asarray(x, jnp.float32 if name.endswith("_norm")
                           else jnp.bfloat16)
    return {**{k: cast(k, params[k])
               for k in ("embed", "head", "final_norm")},
            "layers": [{k: cast(k, v) for k, v in layer.items()}
                       for layer in params["layers"]]}


def decay_rates(cfg: SALAConfig, layer: int) -> np.ndarray:
    """``-log(lambda_h)`` of PUBLISHED layer ``layer``'s heads."""
    h = cfg.lightning_nh
    slope = 2.0 ** (-8.0 * (np.arange(h) + 1.0) / h)
    return (slope * (1.0 - layer / (cfg.published_layers - 1) + 1e-5)
            ).astype(np.float32)


# -- the state's description -------------------------------------------------

def state_layout(cfg: SALAConfig, page_size: int,
                 table_len: int = TABLE_LEN) -> Dict[str, Any]:
    """What the :class:`~predictionio_tpu.serving.state_cache.StateCache`
    holds for this model: per lightning layer a float32 ``[heads, hd,
    hd]`` matrix a slot (and the last hidden row); per sparse layer a
    page of ``page_size`` rows, an event a row (its keys by group, then
    its values by group), and the page's ``page_size / stride`` rows of
    pooled keys (by group); a page table a user.  Paged arrays are 2-D,
    the rows of page ``p`` at ``p * rows ...``: a row is written and
    gathered by ONE index, and no other view of a pool is ever formed (a
    view that splits an axis costs a copy of the pool)."""
    if page_size % cfg.block_size:
        raise ValueError("a page is a whole number of blocks")
    hd, kv = cfg.head_dim, cfg.num_key_value_heads
    fixed = {f"s{i}": ((cfg.lightning_nh, cfg.lightning_head_dim,
                        cfg.lightning_head_dim), jnp.float32)
             for i in range(cfg.n_lightning)}
    fixed["h_last"] = ((cfg.hidden_size,), jnp.float32)
    paged = {f"kv{i}": ((page_size, 2 * kv * hd), jnp.bfloat16)
             for i in range(cfg.n_sparse)}
    index = {f"idx{i}": ((page_size // cfg.kernel_stride, kv * hd),
                         jnp.bfloat16) for i in range(cfg.n_sparse)}

    def nbytes(kind):
        return sum(int(np.prod(shape)) * jnp.dtype(dtype).itemsize
                   for shape, dtype in kind.values())

    def allocate(n_slots: int, n_pages: int) -> Dict[str, jax.Array]:
        arrays = {name: jnp.zeros((n_slots,) + shape, dtype)
                  for name, (shape, dtype) in fixed.items()}
        for kind in (paged, index):
            for name, ((rows, width), dtype) in kind.items():
                arrays[name] = jnp.zeros(((1 + n_pages) * rows, width),
                                         dtype)
        return arrays
    return {"fixed_bytes": nbytes(fixed), "paged_bytes": nbytes(paged),
            "index_bytes": nbytes(index), "table_len": table_len,
            "allocate": allocate}


# -- pieces of a layer -------------------------------------------------------

def _from_tiles(o: jax.Array, batch: Dict[str, jax.Array]) -> jax.Array:
    """[tiles, heads, tq, hd] -> [T, heads * hd]."""
    t = batch["tok_tile"].shape[0]
    return o[batch["tok_tile"], :, batch["tok_in_tile"]].reshape(t, -1)


def lightning_op(cfg: SALAConfig, p: Dict[str, jax.Array], u: jax.Array,
                 batch: Dict[str, jax.Array], state: jax.Array, layer: int
                 ) -> Tuple[jax.Array, jax.Array]:
    t, h, hd = u.shape[0], cfg.lightning_nh, cfg.lightning_head_dim
    qkv = _mm(u, p["w_qkv"]).reshape(t, 3, h, hd)
    q = rope(rms(qkv[:, 0], p["q_norm"], cfg.rms_norm_eps),
             batch["tok_pos"], cfg.rope_theta)
    k = rope(rms(qkv[:, 1], p["k_norm"], cfg.rms_norm_eps),
             batch["tok_pos"], cfg.rope_theta)

    def tiled(x):       # [tiles, heads, tq, hd] bfloat16; a tile's rows
        # past its count repeat other rows, which the kernel masks
        return jnp.swapaxes(x.astype(jnp.bfloat16)[batch["tile_tok"]], 1, 2)

    with jax.named_scope("seq_lightning"):
        o, state = sala_kernels.lightning(
            tiled(q), tiled(k), tiled(qkv[:, 2]), state,
            jnp.asarray(decay_rates(cfg, layer)), batch["tile_first"],
            batch["tile_cnt"], batch["tile_read"], batch["tile_write"])
    o = rms(_from_tiles(o, batch), p["o_norm"], cfg.rms_norm_eps)
    return _mm(jax.nn.sigmoid(_mm(u, p["w_z"])) * o, p["w_o"]), state


def _pool_new_windows(cfg: SALAConfig, k_pool: jax.Array, idx: jax.Array,
                      batch: Dict[str, jax.Array], page_size: int
                      ) -> jax.Array:
    """The pooled key of every window whose LAST event is new: the mean
    of its ``kernel_size`` keys as the pool now holds them (earlier pages
    and this dispatch's rows), written to the page the window starts
    in."""
    ks, st = cfg.kernel_size, cfg.kernel_stride
    pos = batch["tok_pos"]
    done = (batch["tok_seg"] >= 0) & ((pos + 1) % st == 0) & (pos + 1 >= ks)
    at = jnp.maximum(pos[:, None] - (ks - 1) + jnp.arange(ks)[None, :], 0)
    table = batch["tok_table"]                       # [T, table_len]
    pages = jnp.take_along_axis(table, at // page_size, axis=1)
    rows = k_pool[pages * page_size + at % page_size]    # [T, ks, 2 w]
    pooled = jnp.mean(rows[..., :idx.shape[1]].astype(jnp.float32),
                      axis=1).astype(idx.dtype)
    j = jnp.maximum(pos + 1 - ks, 0) // st
    home = jnp.take_along_axis(table, (j * st // page_size)[:, None],
                               axis=1)[:, 0]
    home = jnp.where(done, home, 0)                  # the scrap page
    per = page_size // st
    return idx.at[home * per + j % per].set(pooled)


def select_blocks(cfg: SALAConfig, q: jax.Array, idx: jax.Array,
                  batch: Dict[str, jax.Array], page_size: int
                  ) -> jax.Array:
    """Block ids each query selected: [tiles, groups, tq, topk] int32.
    ``q`` [tiles, groups, heads, tq, hd] bfloat16 (normed, scaled).  The
    tiles' users' pooled keys are gathered through their page tables, a
    few tiles at a time, and scored as the equations say."""
    nt, kv, heads, tq, hd = q.shape
    st, per = cfg.kernel_stride, cfg.block_size // cfg.kernel_stride
    table = batch["tile_table"]                      # [tiles, table_len]
    n_pages = table.shape[1]
    nj = n_pages * (page_size // st)
    nb = nj // per
    ends = jnp.arange(nj) * st + cfg.kernel_size - 1
    b = jnp.arange(nb)
    pos = batch["tile_pos"]                          # [tiles, tq], -1 pad

    def chunk(args):
        qc, tc, pc = args
        rows = (tc[:, :, None] * (page_size // st)
                + jnp.arange(page_size // st)).reshape(tc.shape[0], nj)
        pooled = idx[rows].reshape(tc.shape[0], nj, kv, hd)
        s = jnp.einsum("cghqd,cjgd->cghqj", qc, pooled,
                       preferred_element_type=jnp.float32)
        seen = (ends[None, None, :] <= pc[:, :, None])[:, None, None]
        s = jnp.where(seen, s, _NEG)
        e = jnp.where(seen, jnp.exp(s - jnp.max(s, -1, keepdims=True)), 0.0)
        a = jnp.sum(e / jnp.maximum(jnp.sum(e, -1, keepdims=True), 1e-30),
                    axis=2)                          # [c, kv, tq, nj]
        main = jnp.max(a.reshape(a.shape[:-1] + (nb, per)), axis=-1)
        prev = jnp.pad(a, ((0, 0),) * 3 + ((1, 0),))[..., :-1]
        score = jnp.maximum(main, prev.reshape(
            a.shape[:-1] + (nb, per))[..., 0])
        bq = (jnp.maximum(pc, 0) // cfg.block_size)[:, None, :, None]
        forced = (b < cfg.init_blocks) | (
            (b <= bq) & (b > bq - cfg.window_size // cfg.block_size))
        score = jnp.where(forced, 1e30, score)
        score = jnp.where(b <= bq, score, _NEG)
        return jax.lax.top_k(score, cfg.topk)[1].astype(jnp.int32)

    c = math.gcd(nt, max(64 // tq, 1))    # ~40 MB of scores a step
    split = lambda x: x.reshape((nt // c, c) + x.shape[1:])  # noqa: E731
    out = jax.lax.map(chunk, (split(q), split(table), split(pos)))
    return out.reshape((nt,) + out.shape[2:])


def _page_lists(cfg: SALAConfig, sel: jax.Array,
                batch: Dict[str, jax.Array], page_size: int, u_max: int
                ) -> Tuple[jax.Array, jax.Array]:
    """The pages each (tile, group) reads: (count [tiles, groups], list
    [tiles, groups, u_max] of ``pool page << PAGE_BITS | index in the
    user's history``, ascending; past the count the scrap page at an
    index no position reaches)."""
    per_page = page_size // cfg.block_size
    table, pos = batch["tile_table"], batch["tile_pos"]
    n_pages = table.shape[1]
    page = jnp.arange(n_pages)
    live = (pos >= 0)[:, None, :, None]
    dense = (pos + 1 <= cfg.dense_len)[:, None, :, None]
    picked = jnp.any((sel // per_page)[..., None] == page, axis=3)
    below = (page <= (pos // page_size)[:, None, :, None])
    want = jnp.any(live & jnp.where(dense, below, picked & below), axis=2)
    order = jnp.sort(jnp.where(want, page, n_pages + page),
                     axis=-1)[..., :u_max]
    listed = order < n_pages
    logical = jnp.where(listed, order, (1 << sala_kernels.PAGE_BITS) - 1)
    phys = jnp.take_along_axis(
        jnp.broadcast_to(table[:, None], want.shape),
        jnp.minimum(order, n_pages - 1), axis=-1)
    pages = jnp.where(listed, phys, 0) << sala_kernels.PAGE_BITS | logical
    return jnp.sum(want, -1).astype(jnp.int32), pages.astype(jnp.int32)


def sparse_op(cfg: SALAConfig, p: Dict[str, jax.Array], u: jax.Array,
              batch: Dict[str, jax.Array], kv_pool: jax.Array,
              idx: jax.Array, page_size: int, tq: int, u_max: int
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(output [T, d], the pools with the new rows written)."""
    t, h, hd = u.shape[0], cfg.num_attention_heads, cfg.head_dim
    kv = cfg.num_key_value_heads
    qkv = _mm(u, p["w_qkv"])
    q = rms(qkv[:, :h * hd].reshape(t, h, hd), p["q_norm"],
            cfg.rms_norm_eps)
    k = rms(qkv[:, h * hd:(h + kv) * hd].reshape(t, kv, hd), p["k_norm"],
            cfg.rms_norm_eps)
    v = qkv[:, (h + kv) * hd:].reshape(t, kv, hd)
    new = jnp.concatenate([k.reshape(t, -1), v.reshape(t, -1)], axis=1)
    kv_pool = kv_pool.at[batch["tok_row"]].set(new.astype(kv_pool.dtype))
    idx = _pool_new_windows(cfg, kv_pool, idx, batch, page_size)
    # [tiles, groups, heads of a group, tq, hd], scaled, bfloat16
    qt = (q * (1.0 / math.sqrt(hd))).astype(jnp.bfloat16)[batch["tile_tok"]]
    nt = qt.shape[0]
    qt = jnp.transpose(qt.reshape(nt, tq, kv, h // kv, hd), (0, 2, 3, 1, 4))
    with jax.named_scope("seq_select"):
        sel = select_blocks(cfg, qt, idx, batch, page_size)
        cnt, pages = _page_lists(cfg, sel, batch, page_size, u_max)
    pos = batch["tile_pos"]
    meta = jnp.full((nt, kv, tq, 128), -1, jnp.int32)
    meta = meta.at[..., :cfg.topk].set(sel)
    meta = meta.at[..., sala_kernels.POS_LANE].set(pos[:, None, :])
    meta = meta.at[..., sala_kernels.DENSE_LANE].set(
        (pos + 1 <= cfg.dense_len).astype(jnp.int32)[:, None, :])
    with jax.named_scope("seq_attention"):
        o = sala_kernels.sparse_attention(
            qt.reshape(nt, kv, (h // kv) * tq, hd), meta, cnt, pages,
            kv_pool, page=page_size, block=cfg.block_size, topk=cfg.topk,
            pb=_pages_per_step(tq))
    o = o.reshape(nt, h, tq, hd)                 # group-major = head order
    out = _mm(jax.nn.sigmoid(_mm(u, p["w_z"])) * _from_tiles(o, batch),
              p["w_o"])
    return out, kv_pool, idx


# -- the device program ------------------------------------------------------

def extend_step(params: Dict[str, Any], state: Dict[str, Any],
                batch: Dict[str, Any], *, cfg: SALAConfig, page_size: int,
                k: int, tq: int, u_max: int
                ) -> Tuple[Dict[str, Any], jax.Array, jax.Array]:
    """One dispatch: the new tokens of ``batch`` through every layer
    against ``state``; returns (state with the new rows written, top-``k``
    scores [R, k], their item ids [R, k]).

    ``state``: the arrays of :func:`state_layout` and ``table`` [users,
    table_len].  ``batch`` (int32): per token ``tokens``, ``tok_seg`` (-1
    = padding), ``tok_pos``, ``tok_row`` (pool row of its k/v),
    ``tok_tile``, ``tok_in_tile``; per segment ``seg_write`` (slot),
    ``seg_last`` (token), ``seg_user`` (page-table row); per tile
    ``tile_seg``, ``tile_start`` (token), ``tile_cnt``, ``tile_first``,
    ``tile_read``, ``tile_write`` (slots); ``new_pages`` [n, 3] (table
    row, index, pool page) of the pages this dispatch's plan handed out;
    ``read_tok`` (-1 = the user's stored last hidden row), ``read_slot``
    [R]."""
    tok_seg = batch["tok_seg"]
    seg_of = jnp.maximum(tok_seg, 0)
    new = batch["new_pages"]
    table = state["table"].at[new[:, 0], new[:, 1]].set(new[:, 2])
    t = tok_seg.shape[0]
    in_tile = jnp.arange(tq, dtype=jnp.int32)[None, :]
    tile_tok = jnp.minimum(batch["tile_start"][:, None] + in_tile, t - 1)
    real = in_tile < batch["tile_cnt"][:, None]
    tile_start_pos = batch["tok_pos"][jnp.minimum(batch["tile_start"], t - 1)]
    batch = dict(
        batch, tile_tok=tile_tok,
        tile_pos=jnp.where(real, tile_start_pos[:, None] + in_tile, -1),
        tok_table=table[batch["seg_user"][seg_of]],
        tile_table=table[batch["seg_user"][batch["tile_seg"]]])
    c = cfg.residual_scale
    x = cfg.scale_emb * params["embed"][batch["tokens"]].astype(jnp.float32)
    new_state = dict(state, table=table)
    li = si = 0
    for i, p in enumerate(params["layers"]):
        u = rms(x, p["op_norm"], cfg.rms_norm_eps)
        if cfg.mixer_types[i] == LIGHTNING:
            out, new_state[f"s{li}"] = lightning_op(
                cfg, p, u, batch, new_state[f"s{li}"], cfg.layer_index[i])
            li += 1
        else:
            out, new_state[f"kv{si}"], new_state[f"idx{si}"] = sparse_op(
                cfg, p, u, batch, new_state[f"kv{si}"],
                new_state[f"idx{si}"], page_size, tq, u_max)
            si += 1
        x = x + c * out
        u = rms(x, p["ffn_norm"], cfg.rms_norm_eps)
        f = cfg.intermediate_size
        hmid = _mm(u, p["w13"])
        x = x + c * _mm(jax.nn.silu(hmid[:, :f]) * hmid[:, f:], p["w2"])
    new_state["h_last"] = state["h_last"].at[batch["seg_write"]].set(
        x[batch["seg_last"]])
    read = batch["read_tok"]
    h = jnp.where((read >= 0)[:, None], x[jnp.maximum(read, 0)],
                  state["h_last"][batch["read_slot"]])
    h = (rms(h, params["final_norm"], cfg.rms_norm_eps)
         / cfg.head_divisor).astype(jnp.bfloat16)
    with jax.named_scope("seq_head"):
        logits = jax.lax.dot_general(
            h, params["head"], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        scores, ids = jax.lax.top_k(logits, k)
    return new_state, scores, ids


# -- the runtime's side: one dispatch's arrays, program and counters ---------

_TOKEN_KEYS = ("tokens", "tok_seg", "tok_pos", "tok_row", "tok_tile",
               "tok_in_tile")
_SEG_KEYS = ("seg_write", "seg_last", "seg_user")
_TILE_KEYS = ("tile_seg", "tile_start", "tile_cnt", "tile_first",
              "tile_read", "tile_write")
_READ_KEYS = ("read_tok", "read_slot")
_VECTOR_KEYS = (_TOKEN_KEYS + _SEG_KEYS + _TILE_KEYS + ("new_pages",)
                + _READ_KEYS)


def selection_counts(cfg: SALAConfig, start, n) -> Dict[str, int]:
    """What ``n`` new events at positions ``start ...`` of a user ask of
    ONE sparse layer (arrays: of several users, summed), counted from
    positions alone: ``dense`` and ``selected`` queries, ``keys`` (events
    a selected query attends to, a group), ``pairs`` ((query, pooled
    key) pairs scored, a group)."""
    start = np.atleast_1d(start).astype(np.int64)
    n = np.atleast_1d(n).astype(np.int64)
    first = np.cumsum(n) - n
    pos = np.repeat(start - first, n) + np.arange(int(n.sum()))
    sel = pos[pos + 1 > cfg.dense_len]
    own = sel % cfg.block_size + 1          # its own block, up to itself
    windows = np.maximum((sel + 1 - cfg.kernel_size)
                         // cfg.kernel_stride + 1, 0)
    return {"dense": int(len(pos) - len(sel)), "selected": int(len(sel)),
            "keys": int(np.minimum((cfg.topk - 1) * cfg.block_size + own,
                                   sel + 1).sum()),
            "pairs": int(windows.sum())}


class SALAStep:
    """What :class:`~predictionio_tpu.models.seq_runtime.SequenceRuntime`
    asks of this backbone: the program of a shape, the int32 vector of a
    dispatch, and the reading of what comes back."""

    token_buckets = TOKEN_BUCKETS
    read_buckets = READ_BUCKETS

    def __init__(self, cfg: SALAConfig):
        self.cfg = cfg
        reg = get_registry()
        self._m_queries = reg.counter(
            "pio_seq_sparse_queries_total",
            "(query, sparse layer) pairs by the path the query's position "
            "put it on: dense (all events) or selected (top-k blocks).",
            ("path",))
        self._m_keys = reg.counter(
            "pio_seq_sparse_keys_total",
            "Events the selected path's queries attended to, summed over "
            "kv groups and sparse layers.")
        self._m_pairs = reg.counter(
            "pio_seq_index_pairs_total",
            "(query, pooled key) pairs the block selection scored, summed "
            "over kv groups and sparse layers.")
        self._m_updates = reg.counter(
            "pio_seq_recurrent_updates_total",
            "(user, lightning layer) recurrent states read and written.")

    def tile(self, t: int) -> int:
        return 8 if t <= 256 else 64

    def shapes(self, t: int, r: int, cache) -> Dict[str, int]:
        """Static sizes of the (t, r) program: segments, tiles, the pages
        a (tile, group) can list, new pages a plan can hand out."""
        tq = self.tile(t)
        cfg, page = self.cfg, cache.page_size
        per_page = page // cfg.block_size
        local = cfg.window_size // cfg.block_size
        # A tile's queries: the dense path's pages, the run of blocks
        # that ends with their own (one more for the tile's span, one
        # page more for where the run starts), the first blocks, and
        # topk less the forced of each query's own choosing.
        u = (-(-cfg.dense_len // page) + -(-(local + 1) // per_page) + 1
             + -(-cfg.init_blocks // per_page)
             + (cfg.topk - cfg.init_blocks - local) * tq)
        pb = _pages_per_step(tq)
        u = min(u, cache.table_len)
        return {"tq": tq, "g": r, "nt": r + t // tq,
                "u_max": -(-u // pb) * pb,
                "np": r + t // cache.page_size + 1}

    def program(self, cache, t: int, r: int, k: int):
        sh = self.shapes(t, r, cache)
        return jax.jit(functools.partial(
            _extend_packed, cfg=self.cfg, page_size=cache.page_size, k=k,
            t=t, r=r, sh=tuple(sorted(sh.items()))), donate_argnums=(1,))

    def batch_vector(self, pack: TurnPack, plan, t: int, r: int, cache
                     ) -> np.ndarray:
        sh = self.shapes(t, r, cache)
        tq, g_pad, nt = sh["tq"], sh["g"], sh["nt"]

        def pad(a, size, fill):
            out = np.full(size, fill, np.int32)
            out[:len(a)] = a
            return out
        seg_start = np.asarray(plan.seg_start, np.int64)
        seg_len = np.asarray(plan.seg_len, np.int64)
        tok_pos = seg_start[pack.tok_seg] + pack.tok_idx
        n_tiles = -(-seg_len // tq)
        tile0 = np.concatenate([[0], np.cumsum(n_tiles)])[:-1]
        tile_seg = np.repeat(np.arange(len(seg_len)), n_tiles)
        tile_in_seg = np.arange(len(tile_seg)) - tile0[tile_seg]
        seg_first_tok = pack.seg_last - (pack.seg_len - 1)
        read_slot = np.asarray(plan.read_slot, np.int32)
        write_slot = np.asarray(plan.write_slot, np.int32)
        new_pages = np.asarray(plan.new_pages, np.int32).reshape(-1, 3)
        new_pad = np.zeros((sh["np"], 3), np.int32)   # row 0: nobody's
        new_pad[:len(new_pages)] = new_pages
        parts = {
            "tokens": pad(pack.tokens, t, 0),
            "tok_seg": pad(pack.tok_seg, t, -1),
            "tok_pos": pad(tok_pos, t, 0),
            "tok_row": pad(plan.rows_of(pack.tok_seg, tok_pos), t, 0),
            "tok_tile": pad(tile0[pack.tok_seg] + pack.tok_idx // tq, t, 0),
            "tok_in_tile": pad(pack.tok_idx % tq, t, 0),
            "seg_write": pad(write_slot, g_pad, cache.SCRAP_SLOT),
            "seg_last": pad(pack.seg_last, g_pad, 0),
            "seg_user": pad(plan.table_row, g_pad, 0),
            "tile_seg": pad(tile_seg, nt, 0),
            "tile_start": pad(seg_first_tok[tile_seg] + tile_in_seg * tq,
                              nt, 0),
            "tile_cnt": pad(np.minimum(
                seg_len[tile_seg] - tile_in_seg * tq, tq), nt, 0),
            "tile_first": pad(tile_in_seg == 0, nt, 1),
            "tile_read": pad(read_slot[tile_seg], nt, cache.ZERO_SLOT),
            "tile_write": pad(write_slot[tile_seg], nt, cache.SCRAP_SLOT),
            "new_pages": new_pad.reshape(-1),
            "read_tok": pad(pack.read_tok, r, 0),
            "read_slot": pad([cache.read_slot(key)
                              for key in pack.read_key], r,
                             cache.ZERO_SLOT),
        }
        return np.concatenate([parts[k] for k in _VECTOR_KEYS])

    def read_out(self, out: np.ndarray, r: int, k: int, plan
                 ) -> Tuple[np.ndarray, np.ndarray]:
        """(scores [r, k], ids [r, k]) of what the program sent back; the
        counters move by what the plan's positions say."""
        n = r * k
        cfg = self.cfg
        total = selection_counts(cfg, plan.seg_start, plan.seg_len)
        ns = cfg.n_sparse
        if total["dense"]:
            self._m_queries.inc(total["dense"] * ns, path="dense")
        if total["selected"]:
            self._m_queries.inc(total["selected"] * ns, path="selected")
            groups = ns * cfg.num_key_value_heads
            self._m_keys.inc(total["keys"] * groups)
            self._m_pairs.inc(total["pairs"] * groups)
        self._m_updates.inc(len(plan.seg_len) * cfg.n_lightning)
        return (out[:n].view(np.float32).reshape(r, k),
                out[n:2 * n].reshape(r, k))


def _extend_packed(params, state, vec, *, cfg: SALAConfig, page_size: int,
                   k: int, t: int, r: int, sh):
    """:func:`extend_step` on a packed batch; scores (as their bits) and
    item ids come back as one int32 vector."""
    sh = dict(sh)
    sizes = ([t] * len(_TOKEN_KEYS) + [sh["g"]] * len(_SEG_KEYS)
             + [sh["nt"]] * len(_TILE_KEYS) + [3 * sh["np"]]
             + [r] * len(_READ_KEYS))
    batch, at = {}, 0
    for name, n in zip(_VECTOR_KEYS, sizes):
        batch[name] = vec[at:at + n]
        at += n
    batch["new_pages"] = batch["new_pages"].reshape(-1, 3)
    state, scores, ids = extend_step(
        params, state, batch, cfg=cfg, page_size=page_size, k=k,
        tq=sh["tq"], u_max=sh["u_max"])
    return state, jnp.concatenate([
        jax.lax.bitcast_convert_type(scores, jnp.int32).reshape(-1),
        ids.astype(jnp.int32).reshape(-1)])


def make_runtime(cfg: SALAConfig, params: Dict[str, Any], *,
                 budget_bytes: int, max_users: int,
                 write_slots: Optional[int] = None,
                 page_size: Optional[int] = None,
                 table_len: int = TABLE_LEN):
    """The device side of a loaded model: serving-precision weights and
    the state cache within its budget, its write side as large as the
    users one program can touch (tests pass a smaller one)."""
    from predictionio_tpu.models.seq_runtime import SequenceRuntime
    from predictionio_tpu.serving.state_cache import PAGE_SIZE, StateCache

    page_size = page_size or PAGE_SIZE
    step = SALAStep(cfg)
    cache = StateCache(
        state_layout(cfg, page_size, table_len), budget_bytes=budget_bytes,
        max_users=max_users, page_size=page_size,
        write_slots=write_slots or step.read_buckets[-1])
    return SequenceRuntime(step, cast_for_serving(params), cache)


def config_from_params(p, vocab_size: int) -> SALAConfig:
    """The backbone's shape from the sequence template's algorithm
    params: every held layer is its own published layer."""
    n = len(p.mixerTypes)
    return SALAConfig(
        vocab_size=vocab_size, hidden_size=p.hiddenSize,
        intermediate_size=p.intermediateSize,
        num_attention_heads=p.numAttentionHeads,
        num_key_value_heads=p.numKeyValueHeads, head_dim=p.headDim,
        lightning_nh=p.numAttentionHeads, lightning_head_dim=p.headDim,
        mixer_types=tuple(p.mixerTypes), layer_index=tuple(range(n)),
        published_layers=max(n, 2),
        **{k: int(v) for k, v in (p.sparseConfig or {}).items()})
