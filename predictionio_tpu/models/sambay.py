"""A sequence backbone of Mamba and sliding-window layers under ONE
full-attention cache that eight layers read, with gated memory units
(the SambaY decoder-hybrid-decoder of Phi-4-mini-flash-reasoning), for
next-item prediction over a user's event history, served from per-user
state of three kinds.

**Equations** (``d`` hidden size; ``LN`` = LayerNorm with gain and bias,
eps ``layer_norm_eps``; ``L`` layers ``l = 0 .. L - 1``, ``L`` a multiple
of 4; the head tied to the embedding; no positional encoding of any
kind).  Block ``l``: ``h = x + Mixer_l(LN(x))``, ``y = h + MLP(LN(h))``,
``MLP(u) = W_2(silu(g) * v)``, ``[g, v] = W_1 u``.  After the last layer
one ``LN``, then ``logits = h E^T``.  Mixer by index (``mb_per_layer`` 2;
the published model has ``L`` = 32, so ``L / 2`` = 16):

* **Mamba** (``l`` even, ``l <= L / 2``; ``E = expand d``, ``N =
  d_state``, ``R = dt_rank``, conv width ``d_conv`` = 4): ``[x, z] = W_in
  u``; ``xc_t = silu(sum_j w_conv[j] * x_{t-3+j} + b_conv)``; ``[r_t,
  B_t, C_t] = W_x xc_t``; ``Delta_t = softplus(W_dt r_t + b_dt)``; ``A =
  -exp(A_log)``; ``h_t = exp(Delta_t (x) A) * h_{t-1} + (Delta_t * xc_t)
  (x) B_t``; ``y_t = h_t C_t + D * xc_t``; ``out = W_out(y_t *
  silu(z_t))``.  Layer ``L / 2`` also hands on its ``y_t`` (before the
  gate) as the MEMORY ``m_t``.
* **Differential attention** (``l`` odd): ``q, k, v`` from ``W_qkv u +
  b``; heads in pairs: ``q1_i, q2_i`` = query heads ``2i, 2i + 1``;
  ``k1_j, k2_j`` and ``V_j = [v_2j, v_2j+1]`` (``2 hd`` wide) = kv heads
  ``2j, 2j + 1``, ``j = i // (H / KV)``; ``A1 = softmax(q1 k1^T /
  sqrt(hd) + mask)``, ``A2`` likewise; ``O_i = RMS_2hd((A1 - lambda A2)
  V_j; g_sub) * (1 - lambda_init)``; ``lambda = exp(lq1 . lk1) - exp(lq2
  . lk2) + lambda_init``, ``lambda_init = 0.8 - 0.6 exp(-0.3 l)``; ``out
  = W_o [O_0 ..] + b_o``.  ``mask``: causal, and for ``l < L / 2`` also
  ``t - s < sliding_window`` (a query reads itself and the
  ``sliding_window - 1`` events before it).  Layer ``L / 2 + 1`` is causal
  over the whole history and its ``k, v`` are THE cache: the odd layers
  after it have ``W_q`` only and attend that layer's ``k, v``.
* **Gated memory unit** (``l`` even, ``l > L / 2``): ``out =
  W_out'(silu(W_in' u) * m_t)``, ``m_t`` of the SAME event from layer
  ``L / 2``.

The plain equations are in
:mod:`predictionio_tpu.models.sambay_reference`; this module computes the
same for a RAGGED batch of new events of several users against each
user's cached state (:func:`extend_step`):

* FIXED, a slot a user: per Mamba layer the float32 scan state (stored
  ``[N, E]``: the channels along the lanes) and the convolution's last
  ``d_conv - 1`` input rows; the last event's hidden row after layer ``L
  / 2`` and its memory row (what a query with no new event answers
  from);
* WINDOW pages: the sliding-window layers' keys and values, which the
  cache takes back once they lie behind the user's window;
* FULL pages: layer ``L / 2 + 1``'s keys and values, which grow with the
  history; a page table a user on the device.

**The decoder split.**  Layers ``0 .. L / 2`` and layer ``L / 2 + 1``'s
key/value projection run on every new event.  Layer ``L / 2 + 1``'s
attention, every later layer, the last norm and the head run on READ
rows alone (a turn's last event): they write no state, a GMU reads the
memory of its own event and the attention reads the cache, so what they
would compute at any other event nobody reads.  A dispatch that ends no
turn (a chunk of a long history) runs none of them.

The new events are cut into TILES of up to ``tq`` events of one user;
both kernels (:mod:`predictionio_tpu.ops.sambay_kernels`) work a tile at
a time, and a read row is a tile of one event for the shared cache's
readers.  A chunk longer than the window writes all of its window rows;
the cache takes the pages the chunk's own end has passed back when the
program has run.

Weights, keys, values and matmul inputs are bfloat16; the residual
stream, norms, softmax, the convolution, ``Delta``, the scan and its
state and every accumulation are float32.  Where the equations split a
product the factors are columns of ONE matrix (``w_in`` = [x | z],
``w_x`` = [r | B | C], ``w_qkv`` = [q | k | v], ``w13`` = [g | v]).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.models.lfm2 import _mm
from predictionio_tpu.models.sambay_reference import lambda_init
from predictionio_tpu.obs import get_registry
from predictionio_tpu.ops import sambay_kernels
from predictionio_tpu.ops.ragged import TurnPack

__all__ = ["SambaYConfig", "init_params", "cast_for_serving", "extend_step",
           "SambaYStep", "state_layout", "attention_counts", "make_runtime",
           "vector_sizes"]

TOKEN_BUCKETS = (256, 1024)
# 0: a dispatch that ends no turn runs no cross-decoder.
READ_BUCKETS = (0, 8, 64)
# Pages a user's device page table holds: 320 x 128 = 40,960 events.
TABLE_LEN = 320
MAMBA, WINDOW, FULL, CROSS, GMU = "mamba", "window", "full", "cross", "gmu"
# Pages the attention kernel fetches a step.
_PB = 4
# Rows of a read's tile: its one event and padding up to a bfloat16 tile
# of the query (16 rows = 4 events x the 4 query rows of a kv pair).
_READ_TQ = 4


@dataclasses.dataclass(frozen=True)
class SambaYConfig:
    """Shape of the backbone; the layer pattern follows from the depth."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    num_hidden_layers: int
    sliding_window: int = 512
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    # The Mamba mixer's sizes (assumed: the modelling code's defaults).
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0          # 0: ceil(hidden_size / 16)

    def __post_init__(self):
        if self.num_hidden_layers % 4 or self.num_hidden_layers < 4:
            raise ValueError("the layer pattern needs a multiple of 4 "
                             "layers (the memory layer is a Mamba layer)")
        if self.mb_per_layer != 2:
            raise ValueError("mb_per_layer other than 2 is not supported")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden size does not divide into heads")
        if self.num_attention_heads % self.num_key_value_heads \
                or self.num_key_value_heads % 2:
            raise ValueError("differential attention pairs the kv heads, "
                             "and query heads divide into kv heads")
        if not self.dt_rank:
            object.__setattr__(self, "dt_rank",
                               -(-self.hidden_size // 16))

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.expand * self.hidden_size

    @property
    def memory_layer(self) -> int:
        return self.num_hidden_layers // 2

    @property
    def full_layer(self) -> int:
        return self.memory_layer + 1

    @property
    def kinds(self) -> Tuple[str, ...]:
        half = self.memory_layer
        return tuple(
            (MAMBA if layer <= half else GMU) if layer % 2 == 0 else
            (WINDOW if layer < half else
             FULL if layer == half + 1 else CROSS)
            for layer in range(self.num_hidden_layers))

    def count(self, kind: str) -> int:
        return sum(k == kind for k in self.kinds)

    @property
    def kv_width(self) -> int:
        """Lanes of an event's row of keys and values."""
        return 2 * self.num_key_value_heads * self.head_dim

    @classmethod
    def from_published(cls, doc: Dict[str, Any], **ssm) -> "SambaYConfig":
        """From the keys of the published ``config.json``; ``ssm``: the
        Mamba sizes the published file does not carry."""
        return cls(
            vocab_size=int(doc["vocab_size"]),
            hidden_size=int(doc["hidden_size"]),
            intermediate_size=int(doc["intermediate_size"]),
            num_attention_heads=int(doc["num_attention_heads"]),
            num_key_value_heads=int(doc["num_key_value_heads"]),
            num_hidden_layers=int(doc["num_hidden_layers"]),
            sliding_window=int(doc["sliding_window"]),
            mb_per_layer=int(doc["mb_per_layer"]),
            layer_norm_eps=float(doc["layer_norm_eps"]),
            **{k: int(v) for k, v in ssm.items()})


# -- weights -----------------------------------------------------------------

_F32 = ("conv_w", "conv_b", "dt_b", "a_log", "d_skip", "lam", "sub_g",
        "b_qkv", "b_q", "b_o")


def layer_shapes(cfg: SambaYConfig, layer: int
                 ) -> Dict[str, Tuple[int, ...]]:
    """Name -> shape of layer ``layer``'s weights."""
    d, f, e = cfg.hidden_size, cfg.intermediate_size, cfg.d_inner
    hd, n = cfg.head_dim, cfg.d_state
    qw = cfg.num_attention_heads * hd
    out: Dict[str, Tuple[int, ...]] = {
        "norm1_g": (d,), "norm1_b": (d,), "norm2_g": (d,), "norm2_b": (d,),
        "w13": (d, 2 * f), "w2": (f, d)}
    kind = cfg.kinds[layer]
    if kind == MAMBA:
        out.update(w_in=(d, 2 * e), conv_w=(cfg.d_conv, e), conv_b=(e,),
                   w_x=(e, cfg.dt_rank + 2 * n), w_dt=(cfg.dt_rank, e),
                   dt_b=(e,), a_log=(n, e), d_skip=(e,), w_out=(e, d))
    elif kind == GMU:
        out.update(w_in=(d, e), w_out=(e, d))
    else:
        if kind == CROSS:
            out.update(w_q=(d, qw), b_q=(qw,))
        else:
            out.update(w_qkv=(d, qw + cfg.kv_width),
                       b_qkv=(qw + cfg.kv_width,))
        out.update(w_o=(qw, d), b_o=(d,), lam=(4, hd), sub_g=(2 * hd,))
    return out


def _draw(key, name: str, shape: Tuple[int, ...], dtype):
    """Seeded weights: products normal / sqrt(fan-in) in ``dtype``; norm
    gains 1 + 0.1 normal, norm and projection biases and the convolution's
    bias 0.1 normal, the lambda vectors 0.1 normal; Mamba's published
    initial values (``A_log = log(1 .. N)``, ``D = 1``, ``b_dt`` the
    inverse softplus of a log-uniform step in [1e-3, 1e-1]), float32."""
    if name == "a_log":
        return jnp.broadcast_to(jnp.log(jnp.arange(
            1, shape[0] + 1, dtype=jnp.float32))[:, None], shape)
    if name == "d_skip":
        return jnp.ones(shape, jnp.float32)
    if name == "dt_b":
        dt = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    x = jax.random.normal(key, shape, jnp.float32)
    if name.endswith("_g"):
        return 1.0 + 0.1 * x
    if name.endswith("_b") or name.startswith("b_") or name == "lam":
        return 0.1 * x
    if name == "conv_w":
        return x / math.sqrt(shape[0])
    return (x / math.sqrt(shape[0])).astype(dtype)


def init_params(cfg: SambaYConfig, key: jax.Array, dtype=jnp.bfloat16
                ) -> Dict[str, Any]:
    d = cfg.hidden_size
    ke, kg, kb = jax.random.split(jax.random.fold_in(key, 1 << 20), 3)
    return {
        "embed": (jax.random.normal(ke, (cfg.vocab_size, d), jnp.float32)
                  / math.sqrt(d)).astype(dtype),
        "final_g": _draw(kg, "final_g", (d,), dtype),
        "final_b": _draw(kb, "final_b", (d,), dtype),
        "layers": [{name: _draw(jax.random.fold_in(
            jax.random.fold_in(key, i), j), name, shape, dtype)
            for j, (name, shape) in enumerate(sorted(
                layer_shapes(cfg, i).items()))}
            for i in range(cfg.num_hidden_layers)],
    }


def cast_for_serving(params: Dict[str, Any]) -> Dict[str, Any]:
    """The serving precision: products bfloat16; norms, biases, the
    convolution, the scan's own parameters and the lambda vectors
    float32."""
    def cast(name, x):
        small = (name in _F32 or name.endswith("_g") or name.endswith("_b"))
        return jnp.asarray(x, jnp.float32 if small else jnp.bfloat16)
    return {**{k: cast(k, params[k])
               for k in ("embed", "final_g", "final_b")},
            "layers": [{k: cast(k, v) for k, v in layer.items()}
                       for layer in params["layers"]]}


# -- the state's description -------------------------------------------------

def state_layout(cfg: SambaYConfig, page_size: int,
                 table_len: int = TABLE_LEN) -> Dict[str, Any]:
    """What the :class:`~predictionio_tpu.serving.state_cache.StateCache`
    holds for this model: per Mamba layer a float32 ``[N, E]`` state and
    the convolution's ``d_conv - 1`` last rows a slot, and the last
    event's hidden and memory rows; per window layer a WINDOW page of
    ``page_size`` rows, an event a row (its keys by head, then its
    values); for the shared cache a FULL page of such rows and a page
    table a user.  Paged arrays are 2-D, the rows of page ``p`` at ``p *
    page_size ...``."""
    n_m, n_w = cfg.count(MAMBA), cfg.count(WINDOW)
    e = cfg.d_inner
    fixed = {}
    for i in range(n_m):
        fixed[f"s{i}"] = ((cfg.d_state, e), jnp.float32)
        fixed[f"c{i}"] = ((cfg.d_conv - 1, e), jnp.float32)
    fixed["h_last"] = ((cfg.hidden_size,), jnp.float32)
    fixed["m_last"] = ((e,), jnp.float32)
    row_bytes = cfg.kv_width * 2

    def allocate(n_slots: int, n_pages: int, n_window_pages: int
                 ) -> Dict[str, jax.Array]:
        arrays = {name: jnp.zeros((n_slots,) + shape, dtype)
                  for name, (shape, dtype) in fixed.items()}
        arrays["kv"] = jnp.zeros(((1 + n_pages) * page_size, cfg.kv_width),
                                 jnp.bfloat16)
        for i in range(n_w):
            arrays[f"wkv{i}"] = jnp.zeros(
                ((1 + n_window_pages) * page_size, cfg.kv_width),
                jnp.bfloat16)
        return arrays
    return {"fixed_bytes": sum(int(np.prod(shape)) * 4
                               for shape, _ in fixed.values()),
            "paged_bytes": page_size * row_bytes,
            "window_bytes": n_w * page_size * row_bytes,
            "window_events": cfg.sliding_window,
            "window_spare_pages": TOKEN_BUCKETS[-1] // page_size + 1,
            "table_len": table_len, "allocate": allocate}


# -- pieces of a layer -------------------------------------------------------

def layer_norm(x: jax.Array, g: jax.Array, b: jax.Array, eps: float
               ) -> jax.Array:
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _mlp(cfg: SambaYConfig, p: Dict[str, jax.Array], x: jax.Array
         ) -> jax.Array:
    f = cfg.intermediate_size
    h = _mm(layer_norm(x, p["norm2_g"], p["norm2_b"], cfg.layer_norm_eps),
            p["w13"])
    return _mm(jax.nn.silu(h[:, :f]) * h[:, f:], p["w2"])


def mamba_op(cfg: SambaYConfig, p: Dict[str, jax.Array], u: jax.Array,
             batch: Dict[str, jax.Array], state: jax.Array, tail: jax.Array
             ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """(output [T, d], the scan's output ``y`` [T, E], the state array,
    the convolution tails)."""
    t, e, n, r = u.shape[0], cfg.d_inner, cfg.d_state, cfg.dt_rank
    w = cfg.d_conv
    xz = _mm(u, p["w_in"])
    x, z = xz[:, :e], xz[:, e:]
    # The row ``j`` events back: this dispatch's where the segment holds
    # it, else the user's stored tail (its last ``w - 1`` rows, oldest
    # first).
    seg = jnp.maximum(batch["tok_seg"], 0)
    old = tail[batch["seg_read"]]                       # [G, w - 1, E]
    both = jnp.concatenate([x, old.reshape(-1, e)], axis=0)
    at = jnp.arange(t)
    conv = p["conv_w"][w - 1] * x
    for j in range(1, w):
        back = batch["tok_idx"] - j
        row = jnp.where(back >= 0, at - j,
                        t + seg * (w - 1) + (w - 1 + back))
        conv = conv + p["conv_w"][w - 1 - j] * both[jnp.maximum(row, 0)]
    xc = jax.nn.silu(conv + p["conv_b"])
    # The new tails: row ``m`` (oldest first) lies ``w - 2 - m`` back from
    # the segment's last event.
    m = jnp.arange(w - 1)[None, :]
    back = batch["seg_len"][:, None] - 1 - (w - 2 - m)
    row = jnp.where(back >= 0, batch["seg_last"][:, None] - (w - 2 - m),
                    t + jnp.arange(old.shape[0])[:, None] * (w - 1)
                    + (w - 1 + back))
    tail = tail.at[batch["seg_write"]].set(both[jnp.clip(row, 0,
                                                         both.shape[0] - 1)])
    proj = _mm(xc, p["w_x"])
    delta = jax.nn.softplus(_mm(proj[:, :r], p["w_dt"]) + p["dt_b"])
    tiles, real = batch["tile_tok"], batch["tile_real"]
    with jax.named_scope("ssm_scan"):
        y, state = sambay_kernels.selective_scan(
            xc[tiles], jnp.where(real[..., None], delta[tiles], 0.0),
            jnp.swapaxes(proj[:, r:r + n][tiles], 1, 2),
            jnp.swapaxes(proj[:, r + n:][tiles], 1, 2),
            -jnp.exp(p["a_log"]), p["d_skip"][None, :], state,
            batch["tile_first"], batch["tile_cnt"], batch["tile_read"],
            batch["tile_write"])
    y = y[batch["tok_tile"], batch["tok_in_tile"]]
    return _mm(y * jax.nn.silu(z), p["w_out"]), y, state, tail


def _lambda(p: Dict[str, jax.Array], layer: int) -> jax.Array:
    lam = p["lam"]
    return (jnp.exp(jnp.dot(lam[0], lam[1]))
            - jnp.exp(jnp.dot(lam[2], lam[3])) + lambda_init(layer))


def diff_attention(cfg: SambaYConfig, p: Dict[str, jax.Array], layer: int,
                   q: jax.Array, qpos: jax.Array, cnt: jax.Array,
                   pages: jax.Array, pool: jax.Array, *, page_size: int,
                   window: int, name: str) -> jax.Array:
    """The heads' outputs [tiles, tq, H * hd] (before ``W_o``) of ``q``
    [tiles, tq, H, hd] float32 at positions ``qpos`` [tiles, tq] (-1:
    padding) over the pages ``pages`` [tiles, u] lists of ``pool``."""
    nt, tq, heads, hd = q.shape
    pairs = cfg.num_key_value_heads // 2
    per = heads // 2 // pairs
    # A kv pair's query rows: (query pair of the group, branch, event);
    # branch 0 scores the pair's first key head, branch 1 its second.
    qq = (q * (1.0 / math.sqrt(hd))).astype(jnp.bfloat16).reshape(
        nt, tq, pairs, per, 2, hd)
    zero = jnp.zeros_like(qq[..., 0, :])
    qq = jnp.stack([jnp.concatenate([qq[..., 0, :], zero], -1),
                    jnp.concatenate([zero, qq[..., 1, :]], -1)], axis=4)
    qq = jnp.transpose(qq, (0, 2, 3, 4, 1, 5)).reshape(
        nt, pairs, per * 2 * tq, 2 * hd)
    rows = jnp.tile(qpos, (1, per * 2))
    o = sambay_kernels.paged_attention(
        qq, rows, cnt, pages, pool, page=page_size, window=window, pb=_PB,
        name=name)
    o = o.reshape(nt, pairs, per, 2, tq, 2 * hd)
    o = o[:, :, :, 0] - _lambda(p, layer) * o[:, :, :, 1]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + cfg.layer_norm_eps) \
        * p["sub_g"] * (1.0 - lambda_init(layer))
    return jnp.transpose(o, (0, 3, 1, 2, 4)).reshape(nt, tq, heads * hd)


def window_op(cfg: SambaYConfig, p: Dict[str, jax.Array], layer: int,
              u: jax.Array, batch: Dict[str, jax.Array], pool: jax.Array,
              page_size: int) -> Tuple[jax.Array, jax.Array]:
    """(output [T, d], the window pool with the new rows written)."""
    t, heads, hd = u.shape[0], cfg.num_attention_heads, cfg.head_dim
    qkv = _mm(u, p["w_qkv"]) + p["b_qkv"]
    pool = pool.at[batch["tok_wrow"]].set(
        qkv[:, heads * hd:].astype(pool.dtype))
    q = qkv[:, :heads * hd].reshape(t, heads, hd)[batch["tile_tok"]]
    with jax.named_scope("window_attention"):
        o = diff_attention(
            cfg, p, layer, q, batch["tile_pos"], batch["tile_wcnt"],
            batch["tile_wpages"], pool, page_size=page_size,
            window=cfg.sliding_window, name="sambay_window_attention")
    o = o[batch["tok_tile"], batch["tok_in_tile"]]
    return _mm(o, p["w_o"]) + p["b_o"], pool


def shared_op(cfg: SambaYConfig, p: Dict[str, jax.Array], layer: int,
              q: jax.Array, batch: Dict[str, jax.Array], pool: jax.Array,
              page_size: int) -> jax.Array:
    """One of the shared cache's readers on the read rows: ``q`` [R, H *
    hd] -> [R, d]."""
    r, heads, hd = q.shape[0], cfg.num_attention_heads, cfg.head_dim
    q = jnp.pad(q.reshape(r, 1, heads, hd),
                ((0, 0), (0, _READ_TQ - 1), (0, 0), (0, 0)))
    with jax.named_scope("shared_attention"):
        o = diff_attention(
            cfg, p, layer, q, batch["read_qpos"], batch["read_cnt"],
            batch["read_pages"], pool, page_size=page_size, window=0,
            name="sambay_shared_attention")
    return _mm(o[:, 0], p["w_o"]) + p["b_o"]


# -- the device program ------------------------------------------------------

def extend_step(params: Dict[str, Any], state: Dict[str, Any],
                batch: Dict[str, Any], *, cfg: SambaYConfig, page_size: int,
                k: int, tq: int) -> Tuple[Dict[str, Any], jax.Array,
                                          jax.Array]:
    """One dispatch: the new tokens of ``batch`` through the self-decoder
    against ``state``, the read rows through the cross-decoder; returns
    (state with the new rows written, top-``k`` scores [R, k], their item
    ids [R, k]).

    ``state``: the arrays of :func:`state_layout` and ``table`` [users,
    table_len].  ``batch`` (int32): per token ``tokens``, ``tok_seg`` (-1
    = padding), ``tok_pos``, ``tok_idx`` (index in its segment),
    ``tok_row`` and ``tok_wrow`` (row of the full and of the window pool
    its keys and values go to), ``tok_tile``, ``tok_in_tile``; per
    segment ``seg_read``, ``seg_write`` (slots), ``seg_last`` (token),
    ``seg_len``; per tile ``tile_start`` (token), ``tile_cnt``,
    ``tile_first``, ``tile_read``, ``tile_write`` (slots), ``tile_wcnt``
    and ``tile_wpages`` [tiles, wp] (the window pages the tile's queries
    reach); ``new_pages`` [n, 3] (table row, index, pool page) of the
    full pages this dispatch's plan handed out; per read ``read_tok`` (-1
    = the user's stored rows), ``read_slot``, ``read_user`` (page-table
    row), ``read_pos`` (the read event's position; -1 = padding)."""
    eps = cfg.layer_norm_eps
    new = batch["new_pages"]
    table = state["table"].at[new[:, 0], new[:, 1]].set(new[:, 2])
    t = batch["tok_seg"].shape[0]
    in_tile = jnp.arange(tq, dtype=jnp.int32)[None, :]
    tile_tok = jnp.minimum(batch["tile_start"][:, None] + in_tile, t - 1)
    real = in_tile < batch["tile_cnt"][:, None]
    start_pos = batch["tok_pos"][jnp.minimum(batch["tile_start"], t - 1)]
    batch = dict(batch, tile_tok=tile_tok, tile_real=real,
                 tile_pos=jnp.where(real, start_pos[:, None] + in_tile, -1))
    new_state = dict(state, table=table)
    x = params["embed"][batch["tokens"]].astype(jnp.float32)
    memory = None
    mi = wi = 0
    for layer in range(cfg.full_layer):
        p = params["layers"][layer]
        u = layer_norm(x, p["norm1_g"], p["norm1_b"], eps)
        if cfg.kinds[layer] == MAMBA:
            out, y, new_state[f"s{mi}"], new_state[f"c{mi}"] = mamba_op(
                cfg, p, u, batch, new_state[f"s{mi}"], new_state[f"c{mi}"])
            mi += 1
            if layer == cfg.memory_layer:
                memory = y
        else:
            out, new_state[f"wkv{wi}"] = window_op(
                cfg, p, layer, u, batch, new_state[f"wkv{wi}"], page_size)
            wi += 1
        x = x + out
        x = x + _mlp(cfg, p, x)
    # The shared cache's rows of every new event; what a later query with
    # no event of its own answers from.
    p = params["layers"][cfg.full_layer]
    qw = cfg.num_attention_heads * cfg.head_dim
    u = layer_norm(x, p["norm1_g"], p["norm1_b"], eps)
    new_state["kv"] = new_state["kv"].at[batch["tok_row"]].set(
        (_mm(u, p["w_qkv"][:, qw:]) + p["b_qkv"][qw:]).astype(jnp.bfloat16))
    new_state["h_last"] = state["h_last"].at[batch["seg_write"]].set(
        x[batch["seg_last"]])
    new_state["m_last"] = state["m_last"].at[batch["seg_write"]].set(
        memory[batch["seg_last"]])
    r = batch["read_tok"].shape[0]
    if r == 0:
        return (new_state, jnp.zeros((0, k), jnp.float32),
                jnp.zeros((0, k), jnp.int32))
    # The cross-decoder, on the read rows alone.
    read = batch["read_tok"]
    fresh = (read >= 0)[:, None]
    x = jnp.where(fresh, x[jnp.maximum(read, 0)],
                  state["h_last"][batch["read_slot"]])
    memory = jnp.where(fresh, memory[jnp.maximum(read, 0)],
                       state["m_last"][batch["read_slot"]])
    pos = batch["read_pos"]
    pages = table[batch["read_user"]]
    batch = dict(
        batch,
        read_qpos=jnp.pad(pos[:, None], ((0, 0), (0, _READ_TQ - 1)),
                          constant_values=-1),
        read_cnt=jnp.where(pos >= 0, pos // page_size + 1, 0),
        read_pages=(pages << sambay_kernels.PAGE_BITS)
        | jnp.arange(pages.shape[1], dtype=jnp.int32)[None, :])
    for layer in range(cfg.full_layer, cfg.num_hidden_layers):
        p = params["layers"][layer]
        u = layer_norm(x, p["norm1_g"], p["norm1_b"], eps)
        if cfg.kinds[layer] == GMU:
            with jax.named_scope("gmu"):
                out = _mm(jax.nn.silu(_mm(u, p["w_in"])) * memory,
                          p["w_out"])
        else:
            q = (_mm(u, p["w_q"]) + p["b_q"]) if cfg.kinds[layer] == CROSS \
                else _mm(u, p["w_qkv"][:, :qw]) + p["b_qkv"][:qw]
            out = shared_op(cfg, p, layer, q, batch, new_state["kv"],
                            page_size)
        x = x + out
        x = x + _mlp(cfg, p, x)
    h = layer_norm(x, params["final_g"], params["final_b"], eps
                   ).astype(jnp.bfloat16)
    with jax.named_scope("seq_head"):
        logits = jax.lax.dot_general(
            h, params["embed"], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        scores, ids = jax.lax.top_k(logits, k)
    return new_state, scores, ids


# -- the runtime's side: one dispatch's arrays, program and counters ---------

_TOKEN_KEYS = ("tokens", "tok_seg", "tok_pos", "tok_idx", "tok_row",
               "tok_wrow", "tok_tile", "tok_in_tile")
_SEG_KEYS = ("seg_read", "seg_write", "seg_last", "seg_len")
_TILE_KEYS = ("tile_start", "tile_cnt", "tile_first", "tile_read",
              "tile_write", "tile_wcnt")
_READ_KEYS = ("read_tok", "read_slot", "read_user", "read_pos")
_VECTOR_KEYS = (_TOKEN_KEYS + _SEG_KEYS + _TILE_KEYS
                + ("tile_wpages", "new_pages") + _READ_KEYS)


def vector_sizes(t: int, r: int, sh: Dict[str, int]) -> Tuple[int, ...]:
    """Length of each of ``_VECTOR_KEYS``'s arrays in the (t, r) program's
    int32 vector (``sh``: :meth:`SambaYStep.shapes`)."""
    return ((t,) * len(_TOKEN_KEYS) + (sh["g"],) * len(_SEG_KEYS)
            + (sh["nt"],) * len(_TILE_KEYS)
            + (sh["nt"] * sh["wp"], 3 * sh["np"]) + (r,) * len(_READ_KEYS))


def attention_counts(cfg: SambaYConfig, start, n, read_pos
                     ) -> Dict[str, int]:
    """What a dispatch asks of ONE window layer and of ONE reader of the
    shared cache, from positions alone: ``window_keys`` (events the new
    events' queries attend to), ``window_rows`` (events whose rows the
    layer reads: per user the window behind its first new event, and its
    new events), ``shared_keys`` (events the read rows attend to: each
    its whole history).  ``start``, ``n``: the segments' first positions
    and lengths; ``read_pos``: the read events' positions."""
    start = np.atleast_1d(start).astype(np.int64)
    n = np.atleast_1d(n).astype(np.int64)
    first = np.cumsum(n) - n
    pos = np.repeat(start - first, n) + np.arange(int(n.sum()))
    w = cfg.sliding_window
    has = n > 0
    return {"window_keys": int(np.minimum(pos + 1, w).sum()),
            "window_rows": int((np.minimum(start, w - 1) + n)[has].sum()),
            "shared_keys": int((np.asarray(read_pos, np.int64) + 1).sum())}


class SambaYStep:
    """What :class:`~predictionio_tpu.models.seq_runtime.SequenceRuntime`
    asks of this backbone: the program of a shape, the int32 vector of a
    dispatch, and the reading of what comes back."""

    token_buckets = TOKEN_BUCKETS
    read_buckets = READ_BUCKETS

    def __init__(self, cfg: SambaYConfig):
        self.cfg = cfg
        reg = get_registry()
        self._m_updates = reg.counter(
            "pio_seq_recurrent_updates_total",
            "(user, recurrent layer) states read and written.")
        self._m_window_keys = reg.counter(
            "pio_seq_window_keys_total",
            "Events the new events' queries attended to in the "
            "sliding-window layers, summed over those layers.")
        self._m_window_rows = reg.counter(
            "pio_seq_window_rows_total",
            "Events whose keys and values a dispatch's sliding-window "
            "layers read (a user's window behind its first new event, and "
            "its new events), summed over those layers.")
        self._m_shared_keys = reg.counter(
            "pio_seq_shared_keys_total",
            "Events the read rows attended to in the shared full-attention "
            "cache, summed over the layers that read it.")
        self._m_cross_rows = reg.counter(
            "pio_seq_cross_rows_total",
            "Rows run through the cross-decoder (a turn's last event "
            "each); the self-decoder runs every new event "
            "(pio_seq_tokens_total).")
        # What the dispatch in flight asked for (batch_vector -> read_out).
        self._asked: Dict[str, int] = {}

    def tile(self, t: int) -> int:
        return 8 if t <= 256 else 64

    def shapes(self, t: int, r: int, cache) -> Dict[str, int]:
        """Static sizes of the (t, r) program: segments (a read each, and
        the one a split turn leaves without), tiles, the window pages a
        tile's queries reach, new full pages a plan can hand out."""
        tq = self.tile(t)
        reach = (self.cfg.sliding_window - 1 + tq - 1) // cache.page_size + 2
        g = max(r, 1) + 1
        return {"tq": tq, "g": g, "nt": g + t // tq,
                "wp": -(-reach // _PB) * _PB,
                "np": g + t // cache.page_size + 1}

    def program(self, cache, t: int, r: int, k: int):
        sh = self.shapes(t, r, cache)
        return jax.jit(functools.partial(
            _extend_packed, cfg=self.cfg, page_size=cache.page_size, k=k,
            t=t, r=r, sh=tuple(sorted(sh.items()))), donate_argnums=(1,))

    def batch_vector(self, pack: TurnPack, plan, t: int, r: int, cache
                     ) -> np.ndarray:
        sh = self.shapes(t, r, cache)
        tq, g_pad, nt, wp = sh["tq"], sh["g"], sh["nt"], sh["wp"]
        page, w = cache.page_size, self.cfg.sliding_window

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
        tile_cnt = np.minimum(seg_len[tile_seg] - tile_in_seg * tq, tq)
        read_slot = np.asarray(plan.read_slot, np.int32)
        write_slot = np.asarray(plan.write_slot, np.int32)
        new_pages = np.asarray(plan.new_pages, np.int32).reshape(-1, 3)
        new_pad = np.zeros((sh["np"], 3), np.int32)   # row 0: nobody's
        new_pad[:len(new_pages)] = new_pages
        # The window pages a tile's queries reach: from the page of the
        # event ``w - 1`` before its first to the page of its last.
        first_pos = seg_start[tile_seg] + tile_in_seg * tq
        lo = np.maximum(first_pos - (w - 1), 0) // page
        hi = (first_pos + tile_cnt - 1) // page
        wpages = np.full((nt, wp), sambay_kernels.PAGE_MASK, np.int32)
        for i, s in enumerate(tile_seg):
            held = np.asarray(plan.seg_wpages[s], np.int64)
            idx = np.arange(lo[i], hi[i] + 1)
            wpages[i, :len(idx)] = (held[idx - plan.seg_wbase[s]]
                                    << sambay_kernels.PAGE_BITS) | idx
        # A read's position: its token's, or the user's last event's.
        read_pos = np.asarray([
            tok_pos[tok] if tok >= 0 else cache.length(key) - 1
            for tok, key in zip(pack.read_tok, pack.read_key)], np.int64)
        parts = {
            "tokens": pad(pack.tokens, t, 0),
            "tok_seg": pad(pack.tok_seg, t, -1),
            "tok_pos": pad(tok_pos, t, 0),
            "tok_idx": pad(pack.tok_idx, t, 0),
            "tok_row": pad(plan.rows_of(pack.tok_seg, tok_pos), t, 0),
            "tok_wrow": pad(plan.window_rows_of(pack.tok_seg, tok_pos), t,
                            0),
            "tok_tile": pad(tile0[pack.tok_seg] + pack.tok_idx // tq, t, 0),
            "tok_in_tile": pad(pack.tok_idx % tq, t, 0),
            "seg_read": pad(read_slot, g_pad, cache.ZERO_SLOT),
            "seg_write": pad(write_slot, g_pad, cache.SCRAP_SLOT),
            "seg_last": pad(pack.seg_last, g_pad, 0),
            "seg_len": pad(seg_len, g_pad, 0),
            "tile_start": pad(seg_first_tok[tile_seg] + tile_in_seg * tq,
                              nt, 0),
            "tile_cnt": pad(tile_cnt, nt, 0),
            "tile_first": pad(tile_in_seg == 0, nt, 1),
            "tile_read": pad(read_slot[tile_seg], nt, cache.ZERO_SLOT),
            "tile_write": pad(write_slot[tile_seg], nt, cache.SCRAP_SLOT),
            "tile_wcnt": pad(hi - lo + 1, nt, 0),
            "tile_wpages": wpages.reshape(-1),
            "new_pages": new_pad.reshape(-1),
            "read_tok": pad(pack.read_tok, r, 0),
            "read_slot": pad([cache.read_slot(key)
                              for key in pack.read_key], r,
                             cache.ZERO_SLOT),
            "read_user": pad([cache.table_row(key)
                              for key in pack.read_key], r, 0),
            "read_pos": pad(read_pos, r, -1),
        }
        self._asked = dict(
            attention_counts(self.cfg, seg_start, seg_len, read_pos),
            reads=len(read_pos))
        return np.concatenate([parts[k] for k in _VECTOR_KEYS])

    def read_out(self, out: np.ndarray, r: int, k: int, plan
                 ) -> Tuple[np.ndarray, np.ndarray]:
        """(scores [r, k], ids [r, k]) of what the program sent back; the
        counters move by what the plan's positions say."""
        cfg, asked = self.cfg, self._asked
        self._m_updates.inc(sum(n > 0 for n in plan.seg_len)
                            * cfg.count(MAMBA))
        if asked.get("window_keys"):
            self._m_window_keys.inc(asked["window_keys"]
                                    * cfg.count(WINDOW))
            self._m_window_rows.inc(asked["window_rows"]
                                    * cfg.count(WINDOW))
        if asked.get("reads"):
            self._m_shared_keys.inc(asked["shared_keys"]
                                    * (1 + cfg.count(CROSS)))
            self._m_cross_rows.inc(asked["reads"])
        n = r * k
        return (out[:n].view(np.float32).reshape(r, k),
                out[n:2 * n].reshape(r, k))


def _extend_packed(params, state, vec, *, cfg: SambaYConfig, page_size: int,
                   k: int, t: int, r: int, sh):
    """:func:`extend_step` on a packed batch; scores (as their bits) and
    item ids come back as one int32 vector."""
    sh = dict(sh)
    batch, at = {}, 0
    for name, n in zip(_VECTOR_KEYS, vector_sizes(t, r, sh)):
        batch[name] = vec[at:at + n]
        at += n
    batch["new_pages"] = batch["new_pages"].reshape(-1, 3)
    batch["tile_wpages"] = batch["tile_wpages"].reshape(sh["nt"], sh["wp"])
    state, scores, ids = extend_step(
        params, state, batch, cfg=cfg, page_size=page_size, k=k,
        tq=sh["tq"])
    return state, jnp.concatenate([
        jax.lax.bitcast_convert_type(scores, jnp.int32).reshape(-1),
        ids.astype(jnp.int32).reshape(-1)])


def make_runtime(cfg: SambaYConfig, params: Dict[str, Any], *,
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
    step = SambaYStep(cfg)
    cache = StateCache(
        state_layout(cfg, page_size, table_len), budget_bytes=budget_bytes,
        max_users=max_users, page_size=page_size,
        write_slots=write_slots or step.read_buckets[-1])
    return SequenceRuntime(step, cast_for_serving(params), cache)


def config_from_params(p, vocab_size: int) -> SambaYConfig:
    """The backbone's shape from the sequence template's algorithm
    params."""
    return SambaYConfig(
        vocab_size=vocab_size, hidden_size=p.hiddenSize,
        intermediate_size=p.intermediateSize,
        num_attention_heads=p.numAttentionHeads,
        num_key_value_heads=p.numKeyValueHeads,
        num_hidden_layers=p.numHiddenLayers,
        sliding_window=p.slidingWindow,
        **{k: int(v) for k, v in (p.ssmConfig or {}).items()})
