"""A sequence backbone of Mamba-2 state-space layers beside grouped-query
attention layers with NO positional encoding (the Granite-4.0-H layer
pattern), for next-item prediction over a user's event history, served
from per-user state of two kinds.

**Equations** (``d`` hidden size; ``RMS_n(x; g) = x / sqrt(mean_n(x^2) +
eps) * g``; the head tied to the embedding):

* Input ``x_0 = embedding_multiplier * E[item]``.  Layer: ``h = x +
  residual_multiplier * Mixer(RMS_d(x))``, ``y = h + residual_multiplier *
  MLP(RMS_d(h))``, ``MLP(u) = W_out(silu(a) * b)``, ``[a | b] = W_in u``.
  Output ``logits = RMS_d(x) E^T / logits_scaling``.
* ``mamba`` (Mamba-2; ``E = mamba_expand d`` = ``H`` heads of ``P``, ``N =
  mamba_d_state``, ONE group of ``B``, ``C``; conv width ``mamba_d_conv``
  with bias): ``[z | xBC | dt] = W_in u`` (``E | E + 2 N | H``); ``xBC_t =
  silu(sum_j w_conv[j] * xBC_{t-3+j} + b_conv)``; ``[x | B | C] = xBC``;
  ``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)`` a head; ``S_t =
  exp(dt_t A_h) S_{t-1} + dt_t x_t[h] (x) B_t`` (``P x N``), ``y_t[h] =
  S_t C_t + D_h x_t[h]``; ``y = RMS_E(y * silu(z); g)`` (the gate first,
  then one norm over all ``E``); ``out = W_out y``.
* ``attention``: ``[q | k | v] = W u`` (no bias, no rotary: "nope"); query
  head ``i`` reads kv head ``i // (heads / kv heads)``; causal softmax of
  ``attention_multiplier * q k^T``; ``out = W_o o``.

The plain equations are in
:mod:`predictionio_tpu.models.granite_h_reference`; this module computes
the same for a RAGGED batch of new events of several users against each
user's cached state (:func:`extend_step`):

* FIXED, a slot a user: per Mamba-2 layer the float32 ``[H, P, N]`` state
  (``N`` along the lanes) and the convolution's last ``mamba_d_conv - 1``
  input rows as ONE row (oldest first, ``(mamba_d_conv - 1) x
  conv_width`` lanes, read and written whole); the last event's hidden
  row (what a query with no new event answers from).  At the published
  sizes a slot is 77.4 MB, so the programs touch at most 32 users
  (``READ_BUCKETS``) and the cache's write pool is that large;
* PAGED: the attention layers' keys and values, an event a row (its keys
  by head, then its values), and a page table a user on the device.

The new events are cut into TILES of up to ``tq`` events of one user.
Every array of a Mamba-2 layer between its two products keeps its
channels along the lanes, rows of them, in the order its reader takes
it: nothing is viewed ``[..., H, P]`` here and nothing is turned
tokens-minor and back.
:func:`predictionio_tpu.ops.granite_h_kernels.ssd_update` runs the
recurrence a tile at a time in its matmul form;
:func:`predictionio_tpu.ops.sambay_kernels.paged_attention` reads a tile's
pages once for all of its queries: a kv-head PAIR's keys are 128 lanes of
a row, a query row is zero outside its own head's half of them, and of
the pair's 128-wide output each row keeps its own head's half.  A
dispatch that ends no turn (a chunk of a long history) runs no head.

Weights, keys, values and matmul inputs are bfloat16; the residual
stream, norms, softmax, the convolution, ``dt``, the Mamba-2 state (STORED
float32) and every accumulation are float32.  Where the equations split a
product the factors are columns of ONE matrix (``w_in`` = [z | xBC | dt],
``w_qkv`` = [q | k | v], ``w13`` = [a | b]).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint

from predictionio_tpu.models.lfm2 import _mm, rms
from predictionio_tpu.models.seq_head import top_k_head
from predictionio_tpu.obs import get_registry
from predictionio_tpu.ops import granite_h_kernels, sambay_kernels
from predictionio_tpu.ops.ragged import TurnPack

__all__ = ["GraniteHConfig", "init_params", "cast_for_serving",
           "extend_step", "GraniteHStep", "state_layout", "make_runtime",
           "vector_sizes"]

TOKEN_BUCKETS = (128, 256, 1024)
# 0: a dispatch that ends no turn runs no head.  32: a slot is 77 MB at the
# published sizes, and the cache's write pool is the largest of these.
READ_BUCKETS = (0, 8, 32)
# Pages a user's device page table holds: 256 x 128 = 32,768 events.
TABLE_LEN = 256
MAMBA, ATTENTION = "mamba", "attention"
# Pages the attention kernel fetches a step.
_PB = 4


@dataclasses.dataclass(frozen=True)
class GraniteHConfig:
    """Shape of the backbone; ``layer_types[i]`` is layer ``i``'s mixer."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int          # the published shared_intermediate_size
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    layer_types: Tuple[str, ...]
    mamba_n_heads: int
    mamba_d_head: int
    mamba_d_state: int
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_n_groups: int = 1
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5

    def __post_init__(self):
        bad = set(self.layer_types) - {MAMBA, ATTENTION}
        if bad:
            raise ValueError(f"unknown layer type(s) {sorted(bad)}: a "
                             f"layer is {MAMBA!r} or {ATTENTION!r}")
        if self.mamba_n_groups != 1:
            raise ValueError("mamba_n_groups other than 1 is not supported")
        if self.mamba_n_heads * self.mamba_d_head != self.d_inner:
            raise ValueError("mamba heads x head size is not expand x "
                             "hidden size")
        if self.num_attention_heads % self.num_key_value_heads \
                or self.num_key_value_heads % 2:
            raise ValueError("query heads divide into kv heads, and the "
                             "attention reads the kv heads in pairs")

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def conv_width(self) -> int:
        """Channels the convolution runs over: x, B and C."""
        return self.d_inner + 2 * self.mamba_d_state

    @property
    def kv_width(self) -> int:
        """Lanes of an event's row of keys and values."""
        return 2 * self.num_key_value_heads * self.head_dim

    def count(self, kind: str) -> int:
        return sum(k == kind for k in self.layer_types)

    @classmethod
    def from_published(cls, doc: Dict[str, Any]) -> "GraniteHConfig":
        """From the keys of the published ``config.json``."""
        if int(doc.get("num_local_experts", 0)):
            raise ValueError("routed experts are not supported: the "
                             "block's feed-forward is the shared one")
        if doc.get("position_embedding_type", "nope") != "nope":
            raise ValueError("the attention layers have no positional "
                             "encoding (position_embedding_type 'nope')")
        heads = int(doc["num_attention_heads"])
        return cls(
            vocab_size=int(doc["vocab_size"]),
            hidden_size=int(doc["hidden_size"]),
            intermediate_size=int(doc["shared_intermediate_size"]),
            num_attention_heads=heads,
            num_key_value_heads=int(doc["num_key_value_heads"]),
            head_dim=int(doc.get("head_dim")
                         or int(doc["hidden_size"]) // heads),
            layer_types=tuple(doc["layer_types"]),
            mamba_n_heads=int(doc["mamba_n_heads"]),
            mamba_d_head=int(doc["mamba_d_head"]),
            mamba_d_state=int(doc["mamba_d_state"]),
            mamba_d_conv=int(doc["mamba_d_conv"]),
            mamba_expand=int(doc["mamba_expand"]),
            mamba_n_groups=int(doc["mamba_n_groups"]),
            embedding_multiplier=float(doc["embedding_multiplier"]),
            residual_multiplier=float(doc["residual_multiplier"]),
            attention_multiplier=float(doc["attention_multiplier"]),
            logits_scaling=float(doc["logits_scaling"]),
            rms_norm_eps=float(doc["rms_norm_eps"]))


# -- weights -----------------------------------------------------------------

_F32 = ("conv_w", "conv_b", "dt_b", "a_log", "d_skip")


def layer_shapes(cfg: GraniteHConfig, layer: int
                 ) -> Dict[str, Tuple[int, ...]]:
    """Name -> shape of layer ``layer``'s weights."""
    d, f, e = cfg.hidden_size, cfg.intermediate_size, cfg.d_inner
    out: Dict[str, Tuple[int, ...]] = {
        "mixer_norm": (d,), "ffn_norm": (d,), "w13": (d, 2 * f),
        "w2": (f, d)}
    if cfg.layer_types[layer] == MAMBA:
        h, cw = cfg.mamba_n_heads, cfg.conv_width
        out.update(w_in=(d, e + cw + h), conv_w=(cfg.mamba_d_conv, cw),
                   conv_b=(cw,), dt_b=(h,), a_log=(h,), d_skip=(h,),
                   gate_norm=(e,), w_out=(e, d))
    else:
        qw = cfg.num_attention_heads * cfg.head_dim
        out.update(w_qkv=(d, qw + cfg.kv_width), w_o=(qw, d))
    return out


def _draw(key, name: str, shape: Tuple[int, ...], dtype):
    """Seeded weights: products normal / sqrt(fan-in) in ``dtype``; norm
    gains 1 + 0.1 normal, the convolution's taps normal / sqrt(width) and
    its bias 0.1 normal; Mamba-2's published initial values (``A`` uniform
    in 1 .. 16, ``D = 1``, ``dt_bias`` the inverse softplus of a
    log-uniform step in [1e-3, 1e-1]), float32."""
    if name == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0,
                                          16.0))
    if name == "d_skip":
        return jnp.ones(shape, jnp.float32)
    if name == "dt_b":
        dt = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    x = jax.random.normal(key, shape, jnp.float32)
    if name.endswith("_norm"):
        return 1.0 + 0.1 * x
    if name == "conv_b":
        return 0.1 * x
    if name == "conv_w":
        return x / math.sqrt(shape[0])
    return (x / math.sqrt(shape[0])).astype(dtype)


def init_params(cfg: GraniteHConfig, key: jax.Array, dtype=jnp.bfloat16
                ) -> Dict[str, Any]:
    d = cfg.hidden_size
    ke, kn = jax.random.split(jax.random.fold_in(key, 1 << 20))
    return {
        "embed": (jax.random.normal(ke, (cfg.vocab_size, d), jnp.float32)
                  / math.sqrt(d)).astype(dtype),
        "final_norm": _draw(kn, "final_norm", (d,), dtype),
        "layers": [{name: _draw(jax.random.fold_in(
            jax.random.fold_in(key, i), j), name, shape, dtype)
            for j, (name, shape) in enumerate(sorted(
                layer_shapes(cfg, i).items()))}
            for i in range(len(cfg.layer_types))],
    }


def cast_for_serving(params: Dict[str, Any]) -> Dict[str, Any]:
    """The serving precision: products bfloat16; norms, the convolution
    and the recurrence's own parameters float32."""
    def cast(name, x):
        small = name in _F32 or name.endswith("_norm")
        return jnp.asarray(x, jnp.float32 if small else jnp.bfloat16)
    return {**{k: cast(k, params[k]) for k in ("embed", "final_norm")},
            "layers": [{k: cast(k, v) for k, v in layer.items()}
                       for layer in params["layers"]]}


# -- the state's description -------------------------------------------------

def state_layout(cfg: GraniteHConfig, page_size: int,
                 table_len: int = TABLE_LEN) -> Dict[str, Any]:
    """What the :class:`~predictionio_tpu.serving.state_cache.StateCache`
    holds for this model: per Mamba-2 layer a float32 ``[H, P, N]`` state
    (``s{i}``) and the convolution's ``mamba_d_conv - 1`` last input rows
    a slot as ONE row of ``(mamba_d_conv - 1) * conv_width`` lanes, oldest
    first (``c{i}``: a slot's row is read and written whole), and the
    last event's hidden row; per attention layer a page of ``page_size``
    rows, an event a row (its keys by head, then its values); a page
    table a user.  Paged arrays are 2-D, the rows of page ``p`` at ``p *
    page_size ...``."""
    fixed = {}
    for i in range(cfg.count(MAMBA)):
        fixed[f"s{i}"] = ((cfg.mamba_n_heads, cfg.mamba_d_head,
                           cfg.mamba_d_state), jnp.float32)
        fixed[f"c{i}"] = (((cfg.mamba_d_conv - 1) * cfg.conv_width,),
                          jnp.float32)
    fixed["h_last"] = ((cfg.hidden_size,), jnp.float32)
    n_attn = cfg.count(ATTENTION)

    def allocate(n_slots: int, n_pages: int) -> Dict[str, jax.Array]:
        arrays = {name: jnp.zeros((n_slots,) + shape, dtype)
                  for name, (shape, dtype) in fixed.items()}
        for i in range(n_attn):
            arrays[f"kv{i}"] = jnp.zeros(
                ((1 + n_pages) * page_size, cfg.kv_width), jnp.bfloat16)
        return arrays
    return {"fixed_bytes": sum(int(np.prod(shape)) * 4
                               for shape, _ in fixed.values()),
            "paged_bytes": n_attn * page_size * cfg.kv_width * 2,
            "table_len": table_len, "allocate": allocate}


# -- pieces of a layer -------------------------------------------------------

def _mlp(cfg: GraniteHConfig, p: Dict[str, jax.Array], x: jax.Array
         ) -> jax.Array:
    f = cfg.intermediate_size
    h = _mm(rms(x, p["ffn_norm"], cfg.rms_norm_eps), p["w13"])
    return _mm(jax.nn.silu(h[:, :f]) * h[:, f:], p["w2"])


def _conv(cfg: GraniteHConfig, p: Dict[str, jax.Array], x: jax.Array,
          batch: Dict[str, jax.Array], tail: jax.Array
          ) -> Tuple[jax.Array, jax.Array]:
    """The causal depthwise convolution of ``x`` [T, C] along each
    segment, continued from the segment's stored ``tail`` (its last ``w -
    1`` input rows, oldest first, one row of ``(w - 1) C`` lanes a slot)
    -> (silu(conv + bias), the tails with the segments' new last rows
    written)."""
    t, cw, w = x.shape[0], x.shape[1], cfg.mamba_d_conv
    # The row ``j`` events back: this dispatch's where the segment holds
    # it, else the user's stored tail.  Below ``x`` the stored rows, the
    # segments' oldest first: row ``m`` of segment ``s`` at ``t + m G + s``.
    # (Gathered a tap: placing the stored rows into shifted copies of
    # ``x`` is a scatter, and a row scattered costs the chip four gathered.)
    seg = jnp.maximum(batch["tok_seg"], 0)
    old = tail[batch["seg_read"]]                       # [G, (w - 1) C]
    g = old.shape[0]
    both = jnp.concatenate(
        [x] + [old[:, m * cw:(m + 1) * cw] for m in range(w - 1)], axis=0)
    at = jnp.arange(t)
    conv = p["conv_w"][w - 1] * x
    for j in range(1, w):
        back = batch["tok_idx"] - j
        row = jnp.where(back >= 0, at - j, t + (w - 1 + back) * g + seg)
        conv = conv + p["conv_w"][w - 1 - j] * both[jnp.maximum(row, 0)]
    # The new tails: row ``m`` (oldest first) lies ``w - 2 - m`` back from
    # the segment's last event.
    rows = []
    for m in range(w - 1):
        back = batch["seg_len"] - 1 - (w - 2 - m)
        row = jnp.where(back >= 0, batch["seg_last"] - (w - 2 - m),
                        t + (w - 1 + back) * g + jnp.arange(g))
        rows.append(both[jnp.clip(row, 0, both.shape[0] - 1)])
    tail = tail.at[batch["seg_write"]].set(jnp.concatenate(rows, axis=1))
    return jax.nn.silu(conv + p["conv_b"]), tail


def mamba_op(cfg: GraniteHConfig, p: Dict[str, jax.Array], u: jax.Array,
             batch: Dict[str, jax.Array], state: jax.Array, tail: jax.Array
             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(output [T, d], the state array, the convolution tails)."""
    e, n = cfg.d_inner, cfg.mamba_d_state
    # Held row-major: ``E + conv_width + H`` columns are no whole number
    # of lane tiles at the published widths (8,512 = 66.5 x 128), and the
    # compiler then prefers the result tokens-minor, which every reader
    # below (rows of channels, all of them) would have to turn back.
    proj = with_layout_constraint(_mm(u, p["w_in"]),
                                  Layout(major_to_minor=(0, 1)))
    z = proj[:, :e]
    xbc, tail = _conv(cfg, p, proj[:, e:e + cfg.conv_width], batch, tail)
    dt = jax.nn.softplus(proj[:, e + cfg.conv_width:] + p["dt_b"])
    tiles, real = batch["tile_tok"], batch["tile_real"]
    x = xbc[:, :e]
    with jax.named_scope("ssd_update"):
        y, state = granite_h_kernels.ssd_update(
            x[tiles], jnp.where(real[..., None], dt[tiles], 0.0),
            xbc[:, e:e + n][tiles], xbc[:, e + n:][tiles],
            -jnp.exp(p["a_log"]), state, batch["tile_first"],
            batch["tile_cnt"], batch["tile_read"], batch["tile_write"])
    # The skip term on the event's own row, ``D`` a number a lane.
    y = y[batch["tok_tile"], batch["tok_in_tile"]] \
        + jnp.repeat(p["d_skip"], cfg.mamba_d_head) * x
    y = rms(y * jax.nn.silu(z), p["gate_norm"], cfg.rms_norm_eps)
    return _mm(y, p["w_out"]), state, tail


def attention_op(cfg: GraniteHConfig, p: Dict[str, jax.Array],
                 u: jax.Array, batch: Dict[str, jax.Array], pool: jax.Array,
                 page_size: int) -> Tuple[jax.Array, jax.Array]:
    """(output [T, d], the pool with the new events' rows written)."""
    t, heads, hd = u.shape[0], cfg.num_attention_heads, cfg.head_dim
    pairs = cfg.num_key_value_heads // 2
    per = heads // cfg.num_key_value_heads
    qkv = _mm(u, p["w_qkv"])
    pool = pool.at[batch["tok_row"]].set(
        qkv[:, heads * hd:].astype(pool.dtype))
    # A kv pair's query rows: (kv head of the pair, query head of the
    # group, event); a row is zero outside its kv head's half of the
    # pair's 2 hd lanes.
    q = (qkv[:, :heads * hd] * cfg.attention_multiplier
         ).astype(jnp.bfloat16).reshape(t, pairs, 2, per, hd)[batch["tile_tok"]]
    nt, tq = q.shape[:2]
    half = jnp.eye(2, dtype=q.dtype)[None, None, None, :, None, :, None]
    q = (q[..., None, :] * half).reshape(nt, tq, pairs, 2 * per, 2 * hd)
    q = jnp.transpose(q, (0, 2, 3, 1, 4)).reshape(
        nt, pairs, 2 * per * tq, 2 * hd)
    with jax.named_scope("gqa_attention"):
        o = sambay_kernels.paged_attention(
            q, jnp.tile(batch["tile_pos"], (1, 2 * per)),
            batch["tile_pages_cnt"], batch["tile_pages"], pool,
            page=page_size, pb=_PB, name="granite_h_gqa_attention")
    o = o.reshape(nt, pairs, 2, per, tq, 2, hd)
    o = jnp.stack([o[:, :, 0, :, :, 0], o[:, :, 1, :, :, 1]], axis=2)
    o = jnp.transpose(o, (0, 4, 1, 2, 3, 5)).reshape(nt, tq, heads * hd)
    return _mm(o[batch["tok_tile"], batch["tok_in_tile"]], p["w_o"]), pool


# -- the device program ------------------------------------------------------

def extend_step(params: Dict[str, Any], state: Dict[str, Any],
                batch: Dict[str, Any], *, cfg: GraniteHConfig,
                page_size: int, k: int, tq: int
                ) -> Tuple[Dict[str, Any], jax.Array, jax.Array]:
    """One dispatch: the new tokens of ``batch`` through every layer
    against ``state``; returns (state with the new rows written, top-``k``
    scores [R, k], their item ids [R, k]).

    ``state``: the arrays of :func:`state_layout` and ``table`` [users,
    table_len].  ``batch`` (int32): per token ``tokens``, ``tok_seg`` (-1
    = padding), ``tok_pos``, ``tok_row`` (pool row of its keys and
    values), ``tok_tile``, ``tok_in_tile``; per segment ``seg_read``,
    ``seg_write`` (slots), ``seg_last`` (token), ``seg_len``; per tile
    ``tile_user`` (page-table row), ``tile_start`` (token), ``tile_cnt``,
    ``tile_first``, ``tile_read``, ``tile_write`` (slots); ``new_pages``
    [n, 3] (table row, index, pool page) of the pages this dispatch's
    plan handed out; per read ``read_tok`` (-1 = the user's stored last
    hidden row), ``read_slot``."""
    new = batch["new_pages"]
    table = state["table"].at[new[:, 0], new[:, 1]].set(new[:, 2])
    t = batch["tok_seg"].shape[0]
    in_tile = jnp.arange(tq, dtype=jnp.int32)[None, :]
    tile_tok = jnp.minimum(batch["tile_start"][:, None] + in_tile, t - 1)
    real = in_tile < batch["tile_cnt"][:, None]
    start_pos = batch["tok_pos"][jnp.minimum(batch["tile_start"], t - 1)]
    last_pos = start_pos + batch["tile_cnt"] - 1
    pages = table[batch["tile_user"]]
    batch = dict(
        batch, tile_tok=tile_tok, tile_real=real,
        tile_pos=jnp.where(real, start_pos[:, None] + in_tile, -1),
        tile_pages_cnt=jnp.where(batch["tile_cnt"] > 0,
                                 last_pos // page_size + 1, 0),
        tile_pages=(pages << sambay_kernels.PAGE_BITS)
        | jnp.arange(pages.shape[1], dtype=jnp.int32)[None, :])
    c = cfg.residual_multiplier
    x = cfg.embedding_multiplier \
        * params["embed"][batch["tokens"]].astype(jnp.float32)
    new_state = dict(state, table=table)
    mi = ai = 0
    for kind, p in zip(cfg.layer_types, params["layers"]):
        u = rms(x, p["mixer_norm"], cfg.rms_norm_eps)
        if kind == MAMBA:
            out, new_state[f"s{mi}"], new_state[f"c{mi}"] = mamba_op(
                cfg, p, u, batch, new_state[f"s{mi}"], new_state[f"c{mi}"])
            mi += 1
        else:
            out, new_state[f"kv{ai}"] = attention_op(
                cfg, p, u, batch, new_state[f"kv{ai}"], page_size)
            ai += 1
        x = x + c * out
        x = x + c * _mlp(cfg, p, x)
    new_state["h_last"] = state["h_last"].at[batch["seg_write"]].set(
        x[batch["seg_last"]])
    read = batch["read_tok"]
    if read.shape[0] == 0:
        return (new_state, jnp.zeros((0, k), jnp.float32),
                jnp.zeros((0, k), jnp.int32))
    h = jnp.where((read >= 0)[:, None], x[jnp.maximum(read, 0)],
                  state["h_last"][batch["read_slot"]])
    h = rms(h, params["final_norm"], cfg.rms_norm_eps) / cfg.logits_scaling
    scores, ids = top_k_head(h, params["embed"], k)
    return new_state, scores, ids


# -- the runtime's side: one dispatch's arrays, program and counters ---------

_TOKEN_KEYS = ("tokens", "tok_seg", "tok_pos", "tok_idx", "tok_row",
               "tok_tile", "tok_in_tile")
_SEG_KEYS = ("seg_read", "seg_write", "seg_last", "seg_len")
_TILE_KEYS = ("tile_user", "tile_start", "tile_cnt", "tile_first",
              "tile_read", "tile_write")
_READ_KEYS = ("read_tok", "read_slot")
_VECTOR_KEYS = _TOKEN_KEYS + _SEG_KEYS + _TILE_KEYS + ("new_pages",) \
    + _READ_KEYS


def vector_sizes(t: int, r: int, sh: Dict[str, int]) -> Tuple[int, ...]:
    """Length of each of ``_VECTOR_KEYS``'s arrays in the (t, r) program's
    int32 vector (``sh``: :meth:`GraniteHStep.shapes`)."""
    return ((t,) * len(_TOKEN_KEYS) + (sh["g"],) * len(_SEG_KEYS)
            + (sh["nt"],) * len(_TILE_KEYS) + (3 * sh["np"],)
            + (r,) * len(_READ_KEYS))


class GraniteHStep:
    """What :class:`~predictionio_tpu.models.seq_runtime.SequenceRuntime`
    asks of this backbone: the program of a shape, the int32 vector of a
    dispatch, and the reading of what comes back."""

    token_buckets = TOKEN_BUCKETS
    read_buckets = READ_BUCKETS

    def __init__(self, cfg: GraniteHConfig):
        self.cfg = cfg
        reg = get_registry()
        self._m_updates = reg.counter(
            "pio_seq_recurrent_updates_total",
            "(user, recurrent layer) states read and written.")
        self._m_keys = reg.counter(
            "pio_seq_attended_keys_total",
            "Events the new events' queries attended to in the "
            "full-attention layers (each its history up to itself), "
            "summed over those layers.")
        self._m_rows = reg.counter(
            "pio_seq_attention_rows_total",
            "Events whose keys and values a dispatch's full-attention "
            "layers read (a user's history up to its last new event, once "
            "a user, layer and dispatch), summed over those layers.")
        # What the dispatch in flight asked for (batch_vector -> read_out):
        # attended keys, rows read.
        self._asked = (0, 0)

    def tile(self, t: int) -> int:
        return 16 if t <= 256 else 64

    def shapes(self, t: int, r: int, cache) -> Dict[str, int]:
        """Static sizes of the (t, r) program: segments (a read each, and
        the one a split turn leaves without), tiles, new pages a plan can
        hand out."""
        tq = self.tile(t)
        g = max(r, 1) + 1
        return {"tq": tq, "g": g, "nt": g + t // tq,
                "np": g + t // cache.page_size + 1}

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
        tile_read, tile_write = read_slot[tile_seg], write_slot[tile_seg]
        # A padding tile names the slots of the last real one and is no
        # user's first: the kernel then moves no state for it, where the
        # zero and scrap slots would cost a slot's read and write.
        idle = (0, tile_read[-1], tile_write[-1]) if len(tile_seg) \
            else (1, cache.ZERO_SLOT, cache.SCRAP_SLOT)
        parts = {
            "tokens": pad(pack.tokens, t, 0),
            "tok_seg": pad(pack.tok_seg, t, -1),
            "tok_pos": pad(tok_pos, t, 0),
            "tok_idx": pad(pack.tok_idx, t, 0),
            "tok_row": pad(plan.rows_of(pack.tok_seg, tok_pos), t, 0),
            "tok_tile": pad(tile0[pack.tok_seg] + pack.tok_idx // tq, t, 0),
            "tok_in_tile": pad(pack.tok_idx % tq, t, 0),
            "seg_read": pad(read_slot, g_pad, cache.ZERO_SLOT),
            "seg_write": pad(write_slot, g_pad, cache.SCRAP_SLOT),
            "seg_last": pad(pack.seg_last, g_pad, 0),
            "seg_len": pad(seg_len, g_pad, 0),
            "tile_user": pad(np.asarray(plan.table_row, np.int32)[tile_seg],
                             nt, 0),
            "tile_start": pad(seg_first_tok[tile_seg] + tile_in_seg * tq,
                              nt, 0),
            "tile_cnt": pad(np.minimum(
                seg_len[tile_seg] - tile_in_seg * tq, tq), nt, 0),
            "tile_first": pad(tile_in_seg == 0, nt, idle[0]),
            "tile_read": pad(tile_read, nt, idle[1]),
            "tile_write": pad(tile_write, nt, idle[2]),
            "new_pages": new_pad.reshape(-1),
            "read_tok": pad(pack.read_tok, r, 0),
            "read_slot": pad([cache.read_slot(key)
                              for key in pack.read_key], r,
                             cache.ZERO_SLOT),
        }
        self._asked = (int((tok_pos + 1).sum()),
                       int((seg_start + seg_len)[seg_len > 0].sum()))
        return np.concatenate([parts[k] for k in _VECTOR_KEYS])

    def read_out(self, out: np.ndarray, r: int, k: int, plan
                 ) -> Tuple[np.ndarray, np.ndarray]:
        """(scores [r, k], ids [r, k]) of what the program sent back; the
        counters move by what the plan's positions say."""
        cfg = self.cfg
        self._m_updates.inc(sum(n > 0 for n in plan.seg_len)
                            * cfg.count(MAMBA))
        keys, rows = self._asked
        if keys:
            self._m_keys.inc(keys * cfg.count(ATTENTION))
            self._m_rows.inc(rows * cfg.count(ATTENTION))
        n = r * k
        return (out[:n].view(np.float32).reshape(r, k),
                out[n:2 * n].reshape(r, k))


def _extend_packed(params, state, vec, *, cfg: GraniteHConfig,
                   page_size: int, k: int, t: int, r: int, sh):
    """:func:`extend_step` on a packed batch; scores (as their bits) and
    item ids come back as one int32 vector."""
    sh = dict(sh)
    batch, at = {}, 0
    for name, n in zip(_VECTOR_KEYS, vector_sizes(t, r, sh)):
        batch[name] = vec[at:at + n]
        at += n
    batch["new_pages"] = batch["new_pages"].reshape(-1, 3)
    state, scores, ids = extend_step(
        params, state, batch, cfg=cfg, page_size=page_size, k=k,
        tq=sh["tq"])
    return state, jnp.concatenate([
        jax.lax.bitcast_convert_type(scores, jnp.int32).reshape(-1),
        ids.astype(jnp.int32).reshape(-1)])


def make_runtime(cfg: GraniteHConfig, params: Dict[str, Any], *,
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
    step = GraniteHStep(cfg)
    cache = StateCache(
        state_layout(cfg, page_size, table_len), budget_bytes=budget_bytes,
        max_users=max_users, page_size=page_size,
        write_slots=write_slots or step.read_buckets[-1])
    return SequenceRuntime(step, cast_for_serving(params), cache)


def config_from_params(p, vocab_size: int) -> GraniteHConfig:
    """The backbone's shape from the sequence template's algorithm
    params: the Mamba-2 sizes that ``ssmConfig`` does not name keep the
    published ratios (heads of ``headDim``, expand 2, a state of twice a
    head)."""
    ssm = {k: int(v) for k, v in (p.ssmConfig or {}).items()}
    expand = ssm.setdefault("mamba_expand", 2)
    d_head = ssm.setdefault("mamba_d_head", p.headDim)
    ssm.setdefault("mamba_n_heads", expand * p.hiddenSize // d_head)
    ssm.setdefault("mamba_d_state", 2 * d_head)
    return GraniteHConfig(
        vocab_size=vocab_size, hidden_size=p.hiddenSize,
        intermediate_size=p.intermediateSize,
        num_attention_heads=p.numAttentionHeads,
        num_key_value_heads=p.numKeyValueHeads, head_dim=p.headDim,
        layer_types=tuple(p.layerTypes),
        embedding_multiplier=p.embeddingMultiplier,
        residual_multiplier=p.residualMultiplier,
        attention_multiplier=p.attentionMultiplier,
        logits_scaling=p.logitsScaling, **ssm)
