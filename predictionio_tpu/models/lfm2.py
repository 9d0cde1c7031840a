"""A sequence backbone of gated short convolutions, grouped-query
attention and routed experts, for next-item prediction over a user's
event history, served from per-user state.

One layer: ``h = x + Op(RMS(x; g_op))``, ``y = h + FF(RMS(h; g_ffn))``;
after the last layer one more ``RMS``, then logits ``= h E^T`` with the
input embedding ``E`` (tied).  ``Op`` is a gated short convolution
(state: the last ``L - 1`` rows of the gated input) or causal
grouped-query attention with per-head RMS of ``q`` and ``k`` and rotary
positions (state: ``k`` and ``v`` of every event); ``FF`` is a gated
MLP in the leading dense layers and, after them, ``num_experts`` such
MLPs of which a token takes the top ``num_experts_per_tok`` by
``sigmoid(u W_g) + b`` and weighs them by the sigmoid alone.  The plain
equations are in :mod:`predictionio_tpu.models.lfm2_reference`; this
module computes the same for a RAGGED batch of new events of several
users against each user's cached state:

* :func:`extend_step` — one device program for a token bucket: embeds
  the new events, runs every layer (conv rows read from and written to
  the users' fixed slots; new ``k``/``v`` rows written into the users'
  pages, attention over the pages block by block with an online
  softmax), and scores the vocabulary at each turn's last event.
* :class:`LFM2Step` — what
  :class:`~predictionio_tpu.models.seq_runtime.SequenceRuntime` (turns ->
  dispatches) asks of a backbone: the program of a shape, a dispatch's
  arrays, the reading of what comes back.

Weights and stored state are bfloat16; the residual stream, the router,
norms, softmax and every accumulation are float32.  Weight layout: where
the equations split a product (``[B, C, X] = split3(u W_in)``,
``silu(u W1) * (u W3)``, ``q, k, v``), the factors are columns of ONE
matrix here (``w_in``, ``w13``, ``w_qkv``) in the order the equations
name them.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.models import seq_runtime
from predictionio_tpu.models.seq_runtime import K_MENU, Turn
from predictionio_tpu.obs import get_registry
from predictionio_tpu.ops.pallas_kernels import pallas_supported
from predictionio_tpu.ops.ragged import TurnPack

__all__ = ["LFM2Config", "init_params", "extend_step", "SequenceRuntime",
           "LFM2Step", "Turn", "TOKEN_BUCKETS", "READ_BUCKETS", "K_MENU"]

# Shapes a program is compiled for: new tokens in a dispatch and turns
# that read an answer (the answer's widths: ``seq_runtime.K_MENU``).
TOKEN_BUCKETS = (64, 256, 1024)
READ_BUCKETS = (8, 64)
# Pages the attention loop takes per step (keys = this x page size).
PAGES_PER_BLOCK = 4
ROUTER_EPS = 1e-6
_NEG = -1e30


@dataclasses.dataclass(frozen=True)
class LFM2Config:
    """Shape of the backbone.  ``layer_types[l]`` is ``"conv"`` or
    ``"full_attention"``; ``dense_ff[l]`` says whether layer ``l`` has the
    dense MLP (the published leading layers) or the routed experts."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_experts: int
    num_experts_per_tok: int
    num_attention_heads: int
    num_key_value_heads: int
    layer_types: Tuple[str, ...]
    dense_ff: Tuple[bool, ...]
    conv_L_cache: int = 3  # noqa: N815 - the published key
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    use_expert_bias: bool = True

    def __post_init__(self):
        if len(self.layer_types) != len(self.dense_ff):
            raise ValueError("layer_types and dense_ff differ in length")
        bad = set(self.layer_types) - {"conv", "full_attention"}
        if bad:
            raise ValueError(f"unknown layer type(s) {sorted(bad)}")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden_size does not divide into heads")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads do not divide into kv heads")
        if self.conv_L_cache != 3:
            raise ValueError("the conv state holds L - 1 = 2 rows; "
                             f"conv_L_cache {self.conv_L_cache} is not 3")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def kv_width(self) -> int:
        return self.num_key_value_heads * self.head_dim

    @property
    def n_conv(self) -> int:
        return sum(t == "conv" for t in self.layer_types)

    @property
    def n_attn(self) -> int:
        return len(self.layer_types) - self.n_conv

    @property
    def n_moe(self) -> int:
        return sum(not d for d in self.dense_ff)

    @classmethod
    def from_published(cls, doc: Dict[str, Any],
                       held_layers: Optional[Sequence[int]] = None
                       ) -> "LFM2Config":
        """From the keys of the published ``config.json``;
        ``held_layers`` picks published layer indices (all by default),
        each keeping its published type and its kind of ``FF``."""
        types = list(doc["layer_types"])
        held = list(range(len(types))) if held_layers is None \
            else [int(i) for i in held_layers]
        rope = doc.get("rope_parameters") or {}
        return cls(
            vocab_size=int(doc["vocab_size"]),
            hidden_size=int(doc["hidden_size"]),
            intermediate_size=int(doc["intermediate_size"]),
            moe_intermediate_size=int(doc["moe_intermediate_size"]),
            num_experts=int(doc["num_experts"]),
            num_experts_per_tok=int(doc["num_experts_per_tok"]),
            num_attention_heads=int(doc["num_attention_heads"]),
            num_key_value_heads=int(doc["num_key_value_heads"]),
            layer_types=tuple(types[i] for i in held),
            dense_ff=tuple(i < int(doc["num_dense_layers"]) for i in held),
            conv_L_cache=int(doc.get("conv_L_cache", 3)),
            norm_eps=float(doc.get("norm_eps", 1e-5)),
            rope_theta=float(rope.get("rope_theta",
                                      doc.get("rope_theta", 1e6))),
            routed_scaling_factor=float(
                doc.get("routed_scaling_factor", 1.0)),
            norm_topk_prob=bool(doc.get("norm_topk_prob", True)),
            use_expert_bias=bool(doc.get("use_expert_bias", True)))


# -- weights -----------------------------------------------------------------

def layer_shapes(cfg: LFM2Config, layer: int) -> Dict[str, Tuple[int, ...]]:
    """Name -> shape of layer ``layer``'s weights."""
    d, hd = cfg.hidden_size, cfg.head_dim
    out: Dict[str, Tuple[int, ...]] = {"op_norm": (d,), "ffn_norm": (d,)}
    if cfg.layer_types[layer] == "conv":
        out.update(w_in=(d, 3 * d), conv_w=(cfg.conv_L_cache, d),
                   w_out=(d, d))
    else:
        out.update(w_qkv=(d, d + 2 * cfg.kv_width), q_norm=(hd,),
                   k_norm=(hd,), w_o=(d, d))
    if cfg.dense_ff[layer]:
        f = cfg.intermediate_size
        out.update(w13=(d, 2 * f), w2=(f, d))
    else:
        e, f = cfg.num_experts, cfg.moe_intermediate_size
        out.update(w_g=(d, e), b=(e,), w13=(e, d, 2 * f), w2=(e, f, d))
    return out


_FLOAT32 = ("op_norm", "ffn_norm", "q_norm", "k_norm", "w_g", "b")


def init_layer(cfg: LFM2Config, key: jax.Array, layer: int,
               dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """Seeded weights of one layer: products normal / sqrt(fan-in) in
    ``dtype``; norm weights 1 + 0.1 normal, the router and its bias
    (normal of width 0.05) float32."""
    out = {}
    for i, (name, shape) in enumerate(sorted(
            layer_shapes(cfg, layer).items())):
        k = jax.random.fold_in(jax.random.fold_in(key, layer), i)
        x = jax.random.normal(k, shape, jnp.float32)
        if name.endswith("_norm"):
            out[name] = 1.0 + 0.1 * x
        elif name == "b":
            out[name] = 0.05 * x
        elif name == "w_g":
            out[name] = x / math.sqrt(shape[0])
        elif name == "conv_w":
            out[name] = (x / math.sqrt(shape[0])).astype(dtype)
        else:
            out[name] = (x / math.sqrt(shape[-2])).astype(dtype)
    return out


def init_params(cfg: LFM2Config, key: jax.Array, dtype=jnp.bfloat16
                ) -> Dict[str, Any]:
    d = cfg.hidden_size
    ke, kn = jax.random.split(jax.random.fold_in(key, 1 << 20))
    return {
        "embed": (jax.random.normal(ke, (cfg.vocab_size, d), jnp.float32)
                  / math.sqrt(d)).astype(dtype),
        "final_norm": 1.0 + 0.1 * jax.random.normal(kn, (d,), jnp.float32),
        "layers": [init_layer(cfg, key, layer, dtype)
                   for layer in range(len(cfg.layer_types))],
    }


def cast_for_serving(params: Dict[str, Any]) -> Dict[str, Any]:
    """The serving precision: products bfloat16, norms, router and bias
    float32."""
    def cast(name, x):
        return jnp.asarray(x, jnp.float32 if name in _FLOAT32
                           or name == "final_norm" else jnp.bfloat16)
    return {"embed": cast("embed", params["embed"]),
            "final_norm": cast("final_norm", params["final_norm"]),
            "layers": [{k: cast(k, v) for k, v in layer.items()}
                       for layer in params["layers"]]}


# -- pieces of a layer -------------------------------------------------------

def rms(x: jax.Array, g: jax.Array, eps: float) -> jax.Array:
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _mm(x: jax.Array, w: jax.Array) -> jax.Array:
    """bfloat16 product, float32 accumulation."""
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


def rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """Rotate-half rotary embedding of ``x`` [T, heads, hd] at ``pos``."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def route(cfg: LFM2Config, u: jax.Array, w_g: jax.Array, b: jax.Array
          ) -> Tuple[jax.Array, jax.Array]:
    """(expert ids [T, k], weights [T, k]) of each token: the top-k of
    ``sigmoid(u W_g) + b`` (the bias picks, it does not weigh), weights
    the sigmoids of the picked over their sum."""
    s = jax.nn.sigmoid(jnp.dot(u.astype(jnp.float32), w_g,
                               precision=jax.lax.Precision.HIGHEST))
    pick = s + b if cfg.use_expert_bias else s
    _, ids = jax.lax.top_k(pick, cfg.num_experts_per_tok)
    w = jnp.take_along_axis(s, ids, axis=1)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, -1, keepdims=True) + ROUTER_EPS)
    return ids, w * cfg.routed_scaling_factor


def grouped_matmul(x: jax.Array, w: jax.Array, group_sizes: jax.Array
                   ) -> jax.Array:
    """Rows of ``x`` [M, K], sorted by group, each times its group's
    ``w[g]`` [K, N]; rows past ``sum(group_sizes)`` are unspecified."""
    if pallas_supported():
        from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

        m, k = x.shape
        n = w.shape[-1]
        # Measured on a v5e (64 groups, K 2048 / 1536, N 3072 / 2048): the
        # whole K in one tile and N in tiles of 1024 reads a touched
        # expert's weights once, at 0.8 of the HBM roofline.
        tiling = (min(128, m), _tile(k, 2048), _tile(n, 1024))
        return gmm(x, w, group_sizes, jnp.float32, tiling=tiling)
    return jax.lax.ragged_dot(x, w, group_sizes,
                              preferred_element_type=jnp.float32)


def _tile(n: int, most: int) -> int:
    """Largest multiple of 128 that divides ``n`` and is at most
    ``most`` (``n`` itself where none does)."""
    best = n
    for t in range(128, min(n, most) + 1, 128):
        if n % t == 0:
            best = t
    return best if best <= most else n


def moe_ff(cfg: LFM2Config, p: Dict[str, jax.Array], u: jax.Array,
           valid: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """The expert layer over tokens ``u`` [T, d] (float32, normed):
    (output [T, d] float32, assignments per expert [E] over the valid
    tokens).  Assignments are sorted by expert and each expert's rows go
    through its own MLP as one group of a grouped product; a padded
    token is given to no expert."""
    t, k, e = u.shape[0], cfg.num_experts_per_tok, cfg.num_experts
    ids, wts = route(cfg, u, p["w_g"], p["b"])
    flat = jnp.where(valid[:, None], ids, e).reshape(-1)
    order = jnp.argsort(flat, stable=True)
    token_of = order // k
    sizes = jnp.bincount(flat, length=e + 1)[:e].astype(jnp.int32)
    x = u.astype(jnp.bfloat16)[token_of]
    with jax.named_scope("moe_experts"):
        h = grouped_matmul(x, p["w13"], sizes)
        f = cfg.moe_intermediate_size
        h = (jax.nn.silu(h[:, :f]) * h[:, f:]).astype(jnp.bfloat16)
        y = grouped_matmul(h, p["w2"], sizes)
    real = jnp.arange(t * k) < jnp.sum(sizes)
    y = jnp.where(real[:, None], y, 0.0) * wts.reshape(-1)[order][:, None]
    return jax.ops.segment_sum(y, token_of, num_segments=t), sizes


def dense_ff(cfg: LFM2Config, p: Dict[str, jax.Array], u: jax.Array
             ) -> jax.Array:
    f = cfg.intermediate_size
    h = _mm(u, p["w13"])
    return _mm(jax.nn.silu(h[:, :f]) * h[:, f:], p["w2"])


def conv_op(cfg: LFM2Config, p: Dict[str, jax.Array], u: jax.Array,
            idx_in_seg: jax.Array, state: jax.Array
            ) -> Tuple[jax.Array, jax.Array]:
    """The gated short convolution over ragged segments.

    ``u`` [T, d] normed tokens, segments contiguous; ``idx_in_seg`` [T]
    each token's index within its segment's new tokens; ``state``
    [T, 2, d] the owning user's cached rows ``(z[n-2], z[n-1])`` (zeros
    for a user with no history), repeated per token.  Returns the
    operator's output [T, d] and, per token, the rows
    ``(z[t-1], z[t])`` that are the user's new state if the token is its
    segment's last."""
    d = cfg.hidden_size
    bcx = _mm(u, p["w_in"])
    z = (bcx[:, :d] * bcx[:, 2 * d:]).astype(jnp.bfloat16)
    s0, s1 = state[:, 0], state[:, 1]
    i = idx_in_seg[:, None]
    z1 = jnp.where(i >= 1, jnp.roll(z, 1, axis=0), s1)
    z2 = jnp.where(i >= 2, jnp.roll(z, 2, axis=0),
                   jnp.where(i == 1, s1, s0))
    w = p["conv_w"].astype(jnp.float32)
    c = (w[0] * z2.astype(jnp.float32) + w[1] * z1.astype(jnp.float32)
         + w[2] * z.astype(jnp.float32))
    out = _mm(bcx[:, d:2 * d] * c, p["w_out"])
    return out, jnp.stack([z1, z], axis=1)


def paged_attention(cfg: LFM2Config, q: jax.Array, k_pool: jax.Array,
                    v_pool: jax.Array, tok_seg: jax.Array,
                    tok_pos: jax.Array, pages: Dict[str, jax.Array],
                    page_size: int) -> jax.Array:
    """Causal attention of the new tokens over their users' pages.

    ``q`` [T, H, hd] (normed, rotated); the pools [n_pages, page, kv *
    hd] already hold the new tokens' rows (kept in that shape: a page is
    gathered whole, and no other view of a pool is ever formed, since a
    view that splits the last axis costs a copy of the pool).  ``pages``: ``ids`` [P] pool
    pages of the users in this dispatch, ``seg`` [P] the segment each
    belongs to, ``base`` [P] the position of each page's first row,
    ``blocks`` how many blocks of ``PAGES_PER_BLOCK`` to walk.  A key
    counts for a token of the same segment at a position not after the
    token's; every other pair is masked, so rows of a page beyond its
    user's length are never read into a result."""
    t, kvh, hd = q.shape[0], cfg.num_key_value_heads, cfg.head_dim
    per_kv = cfg.num_attention_heads // kvh
    rows = PAGES_PER_BLOCK * page_size
    qg = (q * (1.0 / math.sqrt(hd))).astype(jnp.bfloat16).reshape(
        t, kvh, per_kv, hd)
    within = jnp.arange(page_size, dtype=jnp.int32)

    def body(i, carry):
        m, l, acc = carry
        sl = jax.lax.dynamic_slice_in_dim
        ids = sl(pages["ids"], i * PAGES_PER_BLOCK, PAGES_PER_BLOCK)
        seg = sl(pages["seg"], i * PAGES_PER_BLOCK, PAGES_PER_BLOCK)
        base = sl(pages["base"], i * PAGES_PER_BLOCK, PAGES_PER_BLOCK)
        kb = k_pool[ids].reshape(rows, kvh, hd)
        vb = v_pool[ids].reshape(rows, kvh, hd)
        s = jnp.einsum("tgqd,sgd->gqts", qg, kb,
                       preferred_element_type=jnp.float32)
        key_pos = (base[:, None] + within[None, :]).reshape(rows)
        key_seg = jnp.repeat(seg, page_size)
        ok = ((key_seg[None, :] == tok_seg[:, None])
              & (key_pos[None, :] <= tok_pos[:, None]))[None, None]
        s = jnp.where(ok, s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, -1))
        pr = jnp.where(ok, jnp.exp(s - m_new[..., None]), 0.0)
        scale = jnp.exp(m - m_new)
        l = l * scale + jnp.sum(pr, -1)
        acc = acc * scale[..., None] + jnp.einsum(
            "gqts,sgd->gqtd", pr.astype(jnp.bfloat16), vb,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    init = (jnp.full((kvh, per_kv, t), _NEG, jnp.float32),
            jnp.zeros((kvh, per_kv, t), jnp.float32),
            jnp.zeros((kvh, per_kv, t, hd), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, pages["blocks"], body, init)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.transpose(out, (2, 0, 1, 3)).reshape(t, -1)


def attention_op(cfg: LFM2Config, p: Dict[str, jax.Array], u: jax.Array,
                 batch: Dict[str, jax.Array], k_pool: jax.Array,
                 v_pool: jax.Array, page_size: int
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(output [T, d], the pools with the new tokens' rows written)."""
    t, d, hd = u.shape[0], cfg.hidden_size, cfg.head_dim
    qkv = _mm(u, p["w_qkv"])
    q = qkv[:, :d].reshape(t, cfg.num_attention_heads, hd)
    k = qkv[:, d:d + cfg.kv_width].reshape(t, cfg.num_key_value_heads, hd)
    v = qkv[:, d + cfg.kv_width:]
    q = rope(rms(q, p["q_norm"], cfg.norm_eps), batch["tok_pos"],
             cfg.rope_theta)
    k = rope(rms(k, p["k_norm"], cfg.norm_eps), batch["tok_pos"],
             cfg.rope_theta)
    page, off = batch["tok_row"] // page_size, batch["tok_row"] % page_size
    k_pool = k_pool.at[page, off].set(k.reshape(t, -1).astype(k_pool.dtype))
    v_pool = v_pool.at[page, off].set(v.astype(v_pool.dtype))
    with jax.named_scope("seq_attention"):
        o = paged_attention(cfg, q, k_pool, v_pool, batch["tok_seg"],
                            batch["tok_pos"], batch["pages"], page_size)
    return _mm(o, p["w_o"]), k_pool, v_pool


# -- the device program ------------------------------------------------------

def extend_step(params: Dict[str, Any], state: Dict[str, Any],
                batch: Dict[str, Any], *, cfg: LFM2Config, page_size: int,
                k: int) -> Tuple[Dict[str, Any], jax.Array, jax.Array,
                                 jax.Array]:
    """One dispatch: the new tokens of ``batch`` through every layer
    against ``state``; returns (state with the new rows written, top-``k``
    scores [R, k], their item ids [R, k], per expert layer the
    assignments of each expert [n_moe, E]).

    ``state``: ``conv`` [n_conv, slots, 2, d] and ``h_last`` [slots, d]
    (fixed slots; a user reads one slot and writes another), ``k`` and
    ``v``: a pool per attention layer.  ``batch`` (int32): ``tokens``,
    ``tok_seg`` (-1 = padding), ``tok_pos``, ``tok_idx`` (index within
    the segment), ``tok_row`` (pool row the token's k/v go to) [T];
    ``seg_read``, ``seg_write`` (slots), ``seg_last`` (token index) [G];
    ``pages``; ``read_tok`` (token whose scores a turn wants, -1 = the
    user's stored last hidden row), ``read_slot`` [R]."""
    tok_seg = batch["tok_seg"]
    valid = tok_seg >= 0
    seg_of = jnp.maximum(tok_seg, 0)
    x = params["embed"][batch["tokens"]].astype(jnp.float32)
    conv, kp, vp = state["conv"], list(state["k"]), list(state["v"])
    ci = ai = 0
    loads = []
    for layer, p in enumerate(params["layers"]):
        u = rms(x, p["op_norm"], cfg.norm_eps)
        if cfg.layer_types[layer] == "conv":
            with jax.named_scope("seq_conv"):
                rows = conv[ci][batch["seg_read"]][seg_of]
                out, new_rows = conv_op(cfg, p, u, batch["tok_idx"], rows)
                conv = conv.at[ci, batch["seg_write"]].set(
                    new_rows[batch["seg_last"]])
            ci += 1
        else:
            out, kp[ai], vp[ai] = attention_op(
                cfg, p, u, batch, kp[ai], vp[ai], page_size)
            ai += 1
        x = x + out
        u = rms(x, p["ffn_norm"], cfg.norm_eps)
        if cfg.dense_ff[layer]:
            x = x + dense_ff(cfg, p, u)
        else:
            y, sizes = moe_ff(cfg, p, u, valid)
            loads.append(sizes)
            x = x + y
    h_last = state["h_last"].at[batch["seg_write"]].set(
        x[batch["seg_last"]].astype(state["h_last"].dtype))
    read = batch["read_tok"]
    h = jnp.where((read >= 0)[:, None], x[jnp.maximum(read, 0)],
                  state["h_last"][batch["read_slot"]].astype(jnp.float32))
    h = rms(h, params["final_norm"], cfg.norm_eps).astype(jnp.bfloat16)
    with jax.named_scope("seq_head"):
        logits = jax.lax.dot_general(
            h, params["embed"], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        scores, ids = jax.lax.top_k(logits, k)
    new_state = {"conv": conv, "h_last": h_last, "k": kp, "v": vp}
    loads = jnp.stack(loads) if loads else jnp.zeros(
        (0, cfg.num_experts), jnp.int32)
    return new_state, scores, ids, loads


_TOKEN_KEYS = ("tokens", "tok_seg", "tok_pos", "tok_idx", "tok_row",
               "seg_read", "seg_write", "seg_last")
_PAGE_KEYS = ("ids", "seg", "base")
_READ_KEYS = ("read_tok", "read_slot")


def pack_batch(batch: Dict[str, Any]) -> np.ndarray:
    """The arrays of :func:`extend_step`'s ``batch`` as one int32 vector."""
    return np.concatenate(
        [batch[k] for k in _TOKEN_KEYS]
        + [batch["pages"][k] for k in _PAGE_KEYS]
        + [batch[k] for k in _READ_KEYS]
        + [np.asarray([batch["pages"]["blocks"]], np.int32)])


def unpack_batch(vec: jax.Array, t: int, r: int, p_len: int
                 ) -> Dict[str, Any]:
    sizes = [t] * len(_TOKEN_KEYS) + [p_len] * len(_PAGE_KEYS) \
        + [r] * len(_READ_KEYS) + [1]
    parts, at = [], 0
    for n in sizes:
        parts.append(vec[at:at + n])
        at += n
    named = dict(zip(_TOKEN_KEYS + _PAGE_KEYS + _READ_KEYS + ("blocks",),
                     parts))
    batch = {k: named[k] for k in _TOKEN_KEYS + _READ_KEYS}
    batch["pages"] = {**{k: named[k] for k in _PAGE_KEYS},
                      "blocks": named["blocks"][0]}
    return batch


def extend_packed(params, state, vec, *, cfg: LFM2Config, page_size: int,
                  k: int, t: int, r: int, p_len: int):
    """:func:`extend_step` on a packed batch; the answers, item ids and
    expert loads come back as one int32 vector (the scores as their
    bits: integers pass through a TPU unchanged, where small integers
    dressed as float32 are denormals and are flushed to zero)."""
    state, scores, ids, loads = extend_step(
        params, state, unpack_batch(vec, t, r, p_len), cfg=cfg,
        page_size=page_size, k=k)
    return state, jnp.concatenate([
        jax.lax.bitcast_convert_type(scores, jnp.int32).reshape(-1),
        ids.astype(jnp.int32).reshape(-1),
        loads.astype(jnp.int32).reshape(-1)])


# -- the runtime's side ------------------------------------------------------

class LFM2Step:
    """This backbone as the sequence runtime drives it."""

    token_buckets = TOKEN_BUCKETS
    read_buckets = READ_BUCKETS

    def __init__(self, cfg: LFM2Config):
        self.cfg = cfg
        reg = get_registry()
        self._m_assign = reg.counter(
            "pio_moe_assignments_total",
            "Token-to-expert assignments, by expert layer.", ("layer",))
        self._m_touched = reg.counter(
            "pio_moe_experts_touched_total",
            "Experts given at least one token, summed over dispatches, "
            "by expert layer.", ("layer",))
        self._m_slots = reg.counter(
            "pio_moe_expert_slots_total",
            "Experts a dispatch could have given a token (the layer's "
            "experts, once a dispatch), by expert layer.", ("layer",))
        self._m_keys = reg.counter(
            "pio_seq_attended_keys_total",
            "(query, key) pairs the new events' causal attention read in "
            "each attention layer: cached events and the turn's own.")
        self._m_load_max = reg.counter(
            "pio_moe_expert_load_max_total",
            "Assignments of the busiest expert, summed over dispatches, "
            "by expert layer.", ("layer",))

    def program(self, cache, t: int, r: int, k: int):
        return jax.jit(functools.partial(
            extend_packed, cfg=self.cfg, page_size=cache.page_size,
            k=k, t=t, r=r, p_len=cache.page_list_len),
            donate_argnums=(1,))

    def batch_vector(self, pack: TurnPack, plan, t: int, r: int, cache
                     ) -> np.ndarray:
        return pack_batch(_batch_arrays(pack, plan, t, r, cache))

    def read_out(self, out: np.ndarray, r: int, k: int, plan
                 ) -> Tuple[np.ndarray, np.ndarray]:
        n = r * k
        scores = out[:n].view(np.float32).reshape(r, k)
        ids = out[n:2 * n].reshape(r, k)
        loads = out[2 * n:].reshape(self.cfg.n_moe, self.cfg.num_experts)
        self._m_keys.inc(sum(int(n) * int(at) + int(n) * (int(n) + 1) // 2
                             for n, at in zip(plan.seg_len,
                                              plan.seg_start)))
        for j in range(loads.shape[0]):
            layer = str(j)
            self._m_assign.inc(int(loads[j].sum()), layer=layer)
            self._m_touched.inc(int((loads[j] > 0).sum()), layer=layer)
            self._m_slots.inc(loads.shape[1], layer=layer)
            self._m_load_max.inc(int(loads[j].max()), layer=layer)
        return scores, ids


class SequenceRuntime(seq_runtime.SequenceRuntime):
    """The sequence runtime over this backbone."""

    def __init__(self, cfg: LFM2Config, params: Dict[str, Any], cache):
        super().__init__(LFM2Step(cfg), params, cache)


def state_layout(cfg: LFM2Config, page_size: int) -> Dict[str, Any]:
    """What the :class:`~predictionio_tpu.serving.state_cache.StateCache`
    holds for this model, all bfloat16: a slot is the conv layers' last
    ``L - 1`` = 2 rows (layers stacked, ``[n_conv, slots, 2, d]``) and
    the last hidden row; a page is ``page_size`` rows of keys and of
    values an attention layer (a pool a layer, ``[1 + pages, page_size,
    kv width]``)."""
    dtype, d, w = jnp.bfloat16, cfg.hidden_size, cfg.kv_width

    def allocate(n_slots: int, n_pages: int) -> Dict[str, Any]:
        pool = (1 + n_pages, page_size, w)
        return {"conv": jnp.zeros((cfg.n_conv, n_slots, 2, d), dtype),
                "h_last": jnp.zeros((n_slots, d), dtype),
                "k": [jnp.zeros(pool, dtype) for _ in range(cfg.n_attn)],
                "v": [jnp.zeros(pool, dtype) for _ in range(cfg.n_attn)]}
    return {"fixed_bytes": 2 * (2 * cfg.n_conv + 1) * d,
            "paged_bytes": 2 * 2 * cfg.n_attn * page_size * w,
            "allocate": allocate}


def make_runtime(cfg: LFM2Config, params: Dict[str, Any], *,
                 budget_bytes: int, max_users: int) -> SequenceRuntime:
    """The device side of a loaded model: serving-precision weights and
    the state cache within its budget, its write side as large as the
    users one program can touch."""
    from predictionio_tpu.serving.state_cache import PAGE_SIZE, StateCache

    cache = StateCache(
        state_layout(cfg, PAGE_SIZE), budget_bytes=budget_bytes,
        max_users=max_users, write_slots=READ_BUCKETS[-1])
    return SequenceRuntime(cfg, cast_for_serving(params), cache)


def config_from_params(p, vocab_size: int) -> LFM2Config:
    """The backbone's shape from the sequence template's algorithm
    params."""
    return LFM2Config(
        vocab_size=vocab_size, hidden_size=p.hiddenSize,
        intermediate_size=p.intermediateSize,
        moe_intermediate_size=p.moeIntermediateSize,
        num_experts=p.numExperts, num_experts_per_tok=p.numExpertsPerTok,
        num_attention_heads=p.numAttentionHeads,
        num_key_value_heads=p.numKeyValueHeads,
        layer_types=tuple(p.layerTypes),
        dense_ff=tuple(i < p.numDenseLayers
                       for i in range(len(p.layerTypes))))


def _batch_arrays(pack: TurnPack, plan, t: int, r: int, cache
                  ) -> Dict[str, Any]:
    """The int32 arrays of one dispatch, padded to the program's shapes."""
    g_pad = t   # a segment holds at least one token

    def pad(a, size, fill):
        out = np.full(size, fill, np.int32)
        out[:len(a)] = a
        return out
    seg_start = np.asarray(plan.seg_start, np.int64)
    tok_pos = seg_start[pack.tok_seg] + pack.tok_idx
    tok_row = plan.rows_of(pack.tok_seg, tok_pos)
    page_ids, page_seg, page_base = plan.page_list()
    p_len = cache.page_list_len
    blocks = -(-len(page_ids) // PAGES_PER_BLOCK)
    return {
        "tokens": pad(pack.tokens, t, 0),
        "tok_seg": pad(pack.tok_seg, t, -1),
        "tok_pos": pad(tok_pos, t, 0),
        "tok_idx": pad(pack.tok_idx, t, 0),
        "tok_row": pad(tok_row, t, 0),      # page 0 takes the padding
        "seg_read": pad(plan.read_slot, g_pad, cache.ZERO_SLOT),
        "seg_write": pad(plan.write_slot, g_pad, cache.SCRAP_SLOT),
        "seg_last": pad(pack.seg_last, g_pad, 0),
        "pages": {"ids": pad(page_ids, p_len, 0),
                  "seg": pad(page_seg, p_len, -2),
                  "base": pad(page_base, p_len, 0),
                  "blocks": np.int32(blocks)},
        "read_tok": pad(pack.read_tok, r, 0),
        # Read before this dispatch's own writes: only a turn with no
        # token of its key in the pack looks here.
        "read_slot": pad([cache.read_slot(key) for key in pack.read_key],
                         r, cache.ZERO_SLOT),
    }
