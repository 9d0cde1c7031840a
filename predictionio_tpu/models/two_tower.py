"""Two-tower neural retrieval — the TPU-era flagship engine.

Absent in the reference (SURVEY.md §2.2 marks it a new build target from
BASELINE.json config 4): learned user/item embeddings + MLP towers trained
with in-batch sampled-softmax negatives, retrieval = MIPS top-K over item
embeddings.

TPU design:
- batch sharded over the ``data`` mesh axis (DP); the in-batch-negatives
  logits matrix is [B, B] — each shard computes its slice against the
  all-gathered item embeddings of the global batch (XLA inserts the
  all-gather from the sharding annotations; it rides ICI).
- embedding tables row-sharded over the ``model`` axis (the tables dominate
  memory); MLP weights replicated (tiny).
- matmuls in bfloat16 with f32 accumulation (MXU-native), params in f32.
- the whole train step is ONE jitted function: grads via ``jax.grad``,
  optax adam update inside.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from predictionio_tpu.obs.runtime import get_compile_tracker
from predictionio_tpu.ops.topk import top_k_scores
from predictionio_tpu.parallel.mesh import AXIS_DATA, AXIS_MODEL, put_sharded

__all__ = ["TwoTowerConfig", "TwoTowerState", "init_state", "train_step",
           "train_steps_fused", "train", "grow_state", "state_to_host",
           "state_from_host", "encode_users", "encode_items", "retrieve"]


@dataclasses.dataclass
class TwoTowerConfig:
    n_users: int
    n_items: int
    embed_dim: int = 64
    hidden_dims: Tuple[int, ...] = (128,)
    out_dim: int = 64
    learning_rate: float = 1e-3
    temperature: float = 0.05
    batch_size: int = 1024
    epochs: int = 5
    seed: int = 0


def _init_mlp(key, in_dim: int, hidden: Tuple[int, ...], out_dim: int) -> Dict:
    layers = []
    dims = (in_dim, *hidden, out_dim)
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        key, k = jax.random.split(key)
        layers.append({
            "w": jax.random.normal(k, (a, b), jnp.float32) * (2.0 / a) ** 0.5,
            "b": jnp.zeros((b,), jnp.float32),
        })
    return {"layers": layers}


def _mlp(params: Dict, x: jax.Array) -> jax.Array:
    h = x.astype(jnp.bfloat16)
    n = len(params["layers"])
    for i, layer in enumerate(params["layers"]):
        h = jnp.einsum("bd,dh->bh", h, layer["w"].astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
        h = h + layer["b"]
        if i < n - 1:
            h = jax.nn.relu(h)
        h = h.astype(jnp.bfloat16)
    return h.astype(jnp.float32)


def init_params(cfg: TwoTowerConfig) -> Dict:
    key = jax.random.PRNGKey(cfg.seed)
    ku, ki, ku2, ki2 = jax.random.split(key, 4)
    scale = cfg.embed_dim ** -0.5
    return {
        "user_embed": jax.random.normal(ku, (cfg.n_users, cfg.embed_dim)) * scale,
        "item_embed": jax.random.normal(ki, (cfg.n_items, cfg.embed_dim)) * scale,
        "user_mlp": _init_mlp(ku2, cfg.embed_dim, cfg.hidden_dims, cfg.out_dim),
        "item_mlp": _init_mlp(ki2, cfg.embed_dim, cfg.hidden_dims, cfg.out_dim),
    }


@dataclasses.dataclass
class TwoTowerState:
    params: Dict
    opt_state: Any
    step: jax.Array


def _tx(cfg: TwoTowerConfig):
    return optax.adam(cfg.learning_rate)


def init_state(cfg: TwoTowerConfig, mesh: Optional[Mesh] = None) -> TwoTowerState:
    params = init_params(cfg)
    if mesh is not None:
        params = jax.tree_util.tree_map(
            lambda p, sh: put_sharded(p, mesh, sh),
            params, param_shardings(cfg, mesh))
    opt_state = _tx(cfg).init(params)
    return TwoTowerState(params=params, opt_state=opt_state,
                         step=jnp.zeros((), jnp.int32))


def state_to_host(state: TwoTowerState) -> Dict:
    """Host-numpy snapshot of a train state for persistence inside a
    model wrapper (the warm-start carry of ISSUE 10).  Exact f32 values —
    the round-trip is bitwise (test-pinned), so a warm-started
    continuation equals continuing in-process."""
    params, opt_state, step = jax.device_get(
        (state.params, state.opt_state, state.step))
    to_np = lambda t: jax.tree.map(np.asarray, t)  # noqa: E731
    return {"params": to_np(params), "opt_state": to_np(opt_state),
            "step": np.asarray(step)}


def state_from_host(snapshot: Dict) -> TwoTowerState:
    """Rebuild a live state from :func:`state_to_host` output.  Leaves
    stay host-backed numpy; the first dispatch uploads them."""
    return TwoTowerState(
        params=jax.tree.map(jnp.asarray, snapshot["params"]),
        opt_state=jax.tree.map(jnp.asarray, snapshot["opt_state"]),
        step=jnp.asarray(snapshot["step"]))


def grow_state(state: TwoTowerState, cfg: TwoTowerConfig) -> TwoTowerState:
    """Grow the embedding tables for entities first seen in a delta
    window (warm-start refresh, ISSUE 10).

    Existing rows keep their trained values AND their adam moments; new
    rows get a fresh deterministic init (keyed off ``cfg.seed`` and the
    CURRENT table height, so two refreshes growing by different deltas
    never collide on init noise) with zero moments — exactly what a
    cold table row would have seen.  ``cfg`` carries the NEW
    ``n_users``/``n_items``.
    """
    params = dict(state.params)
    scale = cfg.embed_dim ** -0.5

    def grown(table: jax.Array, n_total: int, salt: int) -> jax.Array:
        n_old = table.shape[0]
        if n_total <= n_old:
            return table
        key = jax.random.fold_in(jax.random.PRNGKey(cfg.seed),
                                 salt * 1_000_003 + n_old)
        fresh = jax.random.normal(
            key, (n_total - n_old, cfg.embed_dim)) * scale
        return jnp.concatenate([table, fresh], axis=0)

    params["user_embed"] = grown(params["user_embed"], cfg.n_users, 1)
    params["item_embed"] = grown(params["item_embed"], cfg.n_items, 2)
    # Optimizer moments: a fresh init for the new shapes gives zeroed
    # slots everywhere; copy the old leaves back in (same-shape leaves
    # whole, row-grown tables as a prefix write).
    fresh_opt = _tx(cfg).init(params)

    def merge(old_leaf, fresh_leaf):
        old_leaf = jnp.asarray(old_leaf)
        if old_leaf.shape == jnp.shape(fresh_leaf):
            return old_leaf
        return jnp.asarray(fresh_leaf).at[: old_leaf.shape[0]].set(old_leaf)

    opt_state = jax.tree.map(merge, state.opt_state, fresh_opt)
    return TwoTowerState(params=params, opt_state=opt_state,
                         step=state.step)


def param_shardings(cfg: TwoTowerConfig, mesh: Mesh):
    """Embedding tables row-sharded over ``model``; MLPs replicated."""
    def shard(path_leaf):
        return NamedSharding(mesh, P(AXIS_MODEL, None))

    rep = NamedSharding(mesh, P())
    return {
        "user_embed": shard("user_embed"),
        "item_embed": shard("item_embed"),
        "user_mlp": jax.tree.map(lambda _: rep, init_params(cfg)["user_mlp"]),
        "item_mlp": jax.tree.map(lambda _: rep, init_params(cfg)["item_mlp"]),
    }


def _forward_users(params: Dict, user_ids: jax.Array) -> jax.Array:
    e = params["user_embed"][user_ids]
    z = _mlp(params["user_mlp"], e)
    return z / (jnp.linalg.norm(z, axis=-1, keepdims=True) + 1e-6)


def _forward_items(params: Dict, item_ids: jax.Array) -> jax.Array:
    e = params["item_embed"][item_ids]
    z = _mlp(params["item_mlp"], e)
    return z / (jnp.linalg.norm(z, axis=-1, keepdims=True) + 1e-6)


def _loss(params: Dict, user_ids, item_ids, weights, temperature: float):
    """In-batch sampled softmax: positives on the diagonal.

    Duplicate items inside the batch are masked out of the negatives (the
    standard correction — otherwise a repeated positive is its own negative).
    Weight-0 padding rows (trailing partial batch) are likewise masked out
    of every row's negative columns — otherwise item 0's embedding is
    injected pad-many times as a spurious negative.  Each row keeps its own
    diagonal so no row is fully masked.
    """
    u = _forward_users(params, user_ids)       # [B, D]
    v = _forward_items(params, item_ids)       # [B, D]
    logits = jnp.einsum("bd,cd->bc", u, v,
                        preferred_element_type=jnp.float32) / temperature
    same = item_ids[:, None] == item_ids[None, :]
    pad_col = (weights <= 0.0)[None, :]
    mask = (same | pad_col) & ~jnp.eye(item_ids.shape[0], dtype=bool)
    logits = jnp.where(mask, -1e9, logits)
    labels = jnp.arange(item_ids.shape[0])
    losses = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
    return jnp.sum(losses * weights) / jnp.maximum(jnp.sum(weights), 1.0)


def _step_math(state: Tuple, user_ids, item_ids, weights, cfg) -> Tuple:
    """One optimizer step's pure math — shared VERBATIM by the per-step
    jit and the K-fused ``lax.scan`` body so fused training is the same
    traced computation (tests pin K=1 vs K>1 bitwise on CPU)."""
    params, opt_state, step = state
    loss, grads = jax.value_and_grad(_loss)(params, user_ids, item_ids,
                                            weights, cfg.temperature)
    updates, opt_state = _tx(cfg).update(grads, opt_state, params)
    params = optax.apply_updates(params, updates)
    return (params, opt_state, step + 1), loss


# Batch tensors are donated along with the carried state: each step
# consumes its staged batch exactly once (data/prefetch.py creates fresh
# device buffers per step), so donation lets the allocator reclaim the
# batch memory at dispatch instead of waiting for Python GC — with a
# prefetch queue holding `depth` staged batches, that bounds steady-state
# device memory at (depth + 1) batches instead of growing with GC lag.
# Backends without donation support (CPU) warn the donation was unusable;
# expected there (pyproject filters it for the CPU test suite; anywhere
# donation is real the warning stays audible — it would mean the memory
# bound above is not holding).
_train_step_impl = functools.partial(
    jax.jit, static_argnames=("cfg",), donate_argnums=(0, 1, 2, 3))(
        _step_math)


# K-step fused dispatch (ISSUE 7): ONE XLA program runs K optimizer
# steps via lax.scan over a K-stacked superbatch — the per-step
# dispatch/sync cadence (the ``device_wait`` phase of obs/pipeline.py)
# is paid once per K steps.  The whole superbatch is
# donated like the single-step batch.  Returns the carried state and
# the per-step loss vector [K] — the divergence guard checks every slot
# at the fusion boundary.
@functools.partial(jax.jit, static_argnames=("cfg",),
                   donate_argnums=(0, 1, 2, 3))
def _fused_steps_impl(state: Tuple, user_ids, item_ids, weights,
                      cfg) -> Tuple:
    def body(carry, batch):
        u, i, w = batch
        return _step_math(carry, u, i, w, cfg)

    return jax.lax.scan(body, state, (user_ids, item_ids, weights))


# Compile tracking (obs.runtime): cache growth across a call = an XLA
# compilation, exported as pio_xla_compile_total{fn=...} + shape-churn
# warnings.  The fused entry point tracks under its own name, so a
# fusion-depth change shows up as a named compile, not mystery churn.
_tracked_train_step = get_compile_tracker().wrap(
    "two_tower.train_step", _train_step_impl)
_tracked_fused_steps = get_compile_tracker().wrap(
    "two_tower.train_steps_fused", _fused_steps_impl)


# dataclasses aren't pytrees; tuple in/out keeps jit donation simple.
def train_step(state: TwoTowerState, user_ids, item_ids, weights,
               cfg: TwoTowerConfig) -> Tuple[TwoTowerState, jax.Array]:
    """One optimizer step.  ``state`` AND the batch tensors are donated:
    on donation-capable backends (TPU/GPU) the inputs are consumed — pass
    fresh device buffers per call (as the prefetched train loop does),
    not arrays you reuse afterwards."""
    hcfg = _HashableConfig(cfg)
    (p, o, s), loss = _tracked_train_step(
        (state.params, state.opt_state, state.step),
        user_ids, item_ids, weights, hcfg)
    return TwoTowerState(params=p, opt_state=o, step=s), loss


def train_steps_fused(state: TwoTowerState, user_ids, item_ids, weights,
                      cfg: TwoTowerConfig) -> Tuple[TwoTowerState, jax.Array]:
    """K fused optimizer steps in ONE XLA dispatch.

    The batch tensors carry a leading scan axis ([K, B] / [K, B, ...],
    staged by the prefetcher's superbatch assembly); state and the whole
    superbatch are donated.  Returns the carried state and the per-step
    loss vector [K].  The resulting model state is bitwise-equal to K
    sequential :func:`train_step` calls on the same batches (test-pinned
    on CPU; the observability loss scalars may sit 1 ulp off standalone
    dispatches — XLA fuses a rolled scan body's scalar output path
    differently)."""
    hcfg = _HashableConfig(cfg)
    (p, o, s), losses = _tracked_fused_steps(
        (state.params, state.opt_state, state.step),
        user_ids, item_ids, weights, hcfg)
    return TwoTowerState(params=p, opt_state=o, step=s), losses


class _HashableConfig:
    """Static-arg wrapper: hash by the fields that change compilation."""

    def __init__(self, cfg: TwoTowerConfig):
        self._cfg = cfg
        self._key = (cfg.temperature, cfg.learning_rate)

    def __getattr__(self, name):
        return getattr(self._cfg, name)

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, _HashableConfig) and self._key == other._key


def train(
    user_ids: np.ndarray,
    item_ids: np.ndarray,
    cfg: TwoTowerConfig,
    mesh: Optional[Mesh] = None,
    weights: Optional[np.ndarray] = None,
    *,
    checkpoint_dir=None,
    save_every: int = 0,
    data_source: str = "auto",
    fuse_steps=None,
    warm_state: Optional[TwoTowerState] = None,
) -> TwoTowerState:
    """Minibatch training loop over interaction pairs.

    ``warm_state`` (ISSUE 10): continue from an existing state instead of
    a fresh init — the delta warm-start path.  The state must already
    match ``cfg``'s table heights (grow via :func:`grow_state` first);
    ``user_ids``/``item_ids`` then carry only the delta window's
    interactions.  Identical loop otherwise: same prefetcher, fusion,
    supervision, and checkpoint semantics ride both modes, and the
    result is bitwise what in-process continued training on the same
    batches would produce (test-pinned).

    The trailing ragged batch is padded with weight-0 rows — fixed shapes,
    one compilation (SURVEY.md §7 recompilation discipline).  With
    ``checkpoint_dir`` + ``save_every``, the loop checkpoints via orbax and
    resumes mid-epoch after a crash (deterministic per-epoch shuffles make
    batch order reconstructible, so skipped batches are exact).

    ``data_source``: "feeder" pulls epochs from the native mmap event
    cache (native/feeder.cc — batch assembly in C++, off the Python
    loop); "numpy" keeps host permutation; "auto" uses the feeder when
    the native library builds.  Both sources cover the dataset exactly
    once per epoch with a deterministic per-(seed, epoch) shuffle; only
    the permutation differs (tests/test_native.py pins feeder-vs-numpy
    training equivalence).

    Supervision (resilience/supervision.py): a non-finite loss rolls the
    run back to the last-good checkpoint (bounded retries, then
    ``TrainDiverged`` — a NaN model is never returned/persisted);
    SIGTERM preemption checkpoints and raises ``TrainPreempted``; with
    ``PIO_STEP_TIMEOUT_S`` set, a hung device step fires the watchdog
    instead of blocking forever.

    ``fuse_steps`` (default: env ``PIO_FUSE_STEPS``, else 1): fuse K
    optimizer steps into one XLA dispatch (``lax.scan`` over a K-stacked
    superbatch the prefetcher assembles) — bitwise-equal to K=1,
    dispatch/sync paid once per K steps.  ``"auto"`` starts at 1 and
    grows depth between rounds until the HBM headroom guardrail pushes
    back (data/fusion.py).  Supervision moves to the fusion boundary:
    the watchdog deadline scales by K, the divergence guard checks the
    per-step loss vector, and checkpoints land on window boundaries so a
    rollback target never splits a window.
    """
    from predictionio_tpu.resilience.supervision import (
        DivergenceGuard,
        RollbackRequested,
    )

    # Without a checkpointer a "rollback" is a full deterministic retrain
    # that reproduces the same NaN — terminal immediately (max 0), same
    # policy as als.py.
    can_rollback = bool(checkpoint_dir) and save_every > 0
    guard = DivergenceGuard("two_tower",
                            max_rollbacks=None if can_rollback else 0)
    while True:
        try:
            return _train_attempt(user_ids, item_ids, cfg, mesh, weights,
                                  checkpoint_dir=checkpoint_dir,
                                  save_every=save_every,
                                  data_source=data_source, guard=guard,
                                  fuse_steps=fuse_steps,
                                  warm_state=warm_state)
        except RollbackRequested:
            continue  # re-enter: restore_step fast-forwards to last-good


def _train_attempt(
    user_ids: np.ndarray,
    item_ids: np.ndarray,
    cfg: TwoTowerConfig,
    mesh: Optional[Mesh],
    weights: Optional[np.ndarray],
    *,
    checkpoint_dir,
    save_every: int,
    data_source: str,
    guard,
    fuse_steps=None,
    warm_state: Optional[TwoTowerState] = None,
) -> TwoTowerState:
    from predictionio_tpu.resilience.supervision import (
        StepWatchdog,
        TrainPreempted,
        preemption_requested,
    )
    from predictionio_tpu.workflow.checkpoint import TrainCheckpointer

    n = len(user_ids)
    if weights is None:
        weights = np.ones(n, dtype=np.float32)
    state = warm_state if warm_state is not None else init_state(cfg, mesh)
    total_steps = cfg.epochs * ((n + cfg.batch_size - 1) // cfg.batch_size)
    # Warm continuations fingerprint on the carried step too: a crash-
    # resume checkpoint from a DIFFERENT base generation must not be
    # restored into this delta.
    fp_extra = f"|warm@{int(jax.device_get(state.step))}" \
        if warm_state is not None else ""
    ckpt = TrainCheckpointer(checkpoint_dir or ".", save_every=save_every
                             if checkpoint_dir else 0,
                             fingerprint=f"two_tower|{cfg}|n={n}{fp_extra}")
    watchdog = StepWatchdog("two_tower", checkpoint_fn=ckpt.flush)
    start_step = ckpt.restore_step(
        (state.params, state.opt_state, state.step), total_steps=total_steps)
    if ckpt.restored_state is not None:
        p, o, s = ckpt.restored_state
        state = TwoTowerState(params=p, opt_state=o, step=s)
    bs = cfg.batch_size
    batch_sharding = NamedSharding(mesh, P(AXIS_DATA)) if mesh is not None else None

    def numpy_epochs():
        for epoch in range(cfg.epochs):
            order = np.random.default_rng(cfg.seed + epoch).permutation(n)
            for start in range(0, n, bs):
                sel = order[start:start + bs]
                yield user_ids[sel], item_ids[sel], weights[sel]

    def feeder_epochs():
        import tempfile

        from predictionio_tpu.native.feeder import EventFeeder, write_cache

        with tempfile.TemporaryDirectory(prefix="pio_tt_cache_") as d:
            cache = write_cache(f"{d}/train.piof",
                                np.asarray(user_ids, np.uint32),
                                np.asarray(item_ids, np.uint32),
                                np.asarray(weights, np.float32))
            with EventFeeder(cache, bs, seed=cfg.seed) as f:
                for _ in range(cfg.epochs):
                    yield from f.epoch()

    use_feeder = data_source == "feeder"
    if data_source == "auto":
        from predictionio_tpu.native.build import load_library

        use_feeder = load_library("feeder") is not None
    # Overlapped input pipeline (ISSUE 5 / data/prefetch.py): tail-batch
    # padding + dtype conversion + the device transfer run on a
    # background prep thread, double-buffered, so batch N+1's H2D rides
    # under batch N's device step.  The probe attributes the staging to
    # the overlap window; only the queue wait stays on the step loop.
    # K-step fusion (ISSUE 7 / data/fusion.py): the prefetcher stacks K
    # prepped batches into one superbatch and the loop dispatches ONE
    # lax.scan program per window — supervision sits at the window
    # boundary.
    from predictionio_tpu.data.fusion import (
        FusionAutotuner,
        FusionPlan,
        crossed_save_point,
        fuse_steps_config,
        slot_steps,
    )
    from predictionio_tpu.data.prefetch import DevicePrefetcher
    from predictionio_tpu.obs import PipelineProbe

    def prep(batch):
        # Prep-thread staging: identical layout/dtypes to the historical
        # inline path (tests pin bitwise equivalence on CPU).
        u, i, w = batch
        pad = bs - len(u)
        return (
            np.concatenate([np.asarray(u, np.int64),
                            np.zeros(pad, np.int64)]).astype(np.int32),
            np.concatenate([np.asarray(i, np.int64),
                            np.zeros(pad, np.int64)]).astype(np.int32),
            np.concatenate([np.asarray(w, np.float32),
                            np.zeros(pad, np.float32)]),
        )

    put = None
    fused_put = None
    if batch_sharding is not None:
        def put(arrays):
            return tuple(put_sharded(a, mesh, batch_sharding)
                         for a in arrays)

        # Superbatches carry a leading scan axis: the batch axis moves
        # to dim 1, so the fused staging shards dim 1 and replicates the
        # scan axis.
        fused_sharding = NamedSharding(mesh, P(None, AXIS_DATA))

        def fused_put(arrays):
            return tuple(put_sharded(a, mesh, fused_sharding)
                         for a in arrays)

    k0, auto = fuse_steps_config(fuse_steps)
    plan = FusionPlan(k0)
    tuner = FusionAutotuner("two_tower", plan) if auto else None

    probe = PipelineProbe("two_tower")
    global_step = start_step
    pending = None  # (losses, slot steps) of the in-flight dispatch
    in_flight = 0  # raw steps covered by the in-flight dispatch
    try:
        with DevicePrefetcher(
                feeder_epochs() if use_feeder else numpy_epochs(),
                prep, put_fn=put, fused_put_fn=fused_put,
                skip_steps=start_step, fuse_plan=plan,
                model="two_tower") as pf:
            for batch in probe.iter_prefetched(pf):
                global_step = batch.step
                # Deadline covers the LONGER of the in-flight dispatch
                # (the sync below blocks on dispatch N-1 — possibly a
                # deeper window than this batch, e.g. a K=1 tail flush
                # behind a K=32 window) and this batch's own dispatch.
                watchdog.arm(global_step,
                             scale=max(batch.steps, in_flight))
                probe.sync()  # wait on dispatch N-1: its state feeds N
                if pending is not None:
                    # Dispatch N-1's losses materialized with the sync
                    # above — every slot of its window is checked at the
                    # fusion boundary for one host read of K floats.
                    guard.check_vector(*pending)
                if batch.k > 1:
                    state, losses = train_steps_fused(state, *batch.args,
                                                      cfg)
                else:
                    state, losses = train_step(state, *batch.args, cfg)
                pending = (losses, slot_steps(batch))
                in_flight = batch.steps
                # Sync target includes the losses: the next boundary's
                # divergence check reads them materialized, and the wait
                # bills to device_wait where it belongs.
                probe.dispatched((state, losses), examples=batch.examples,
                                 steps=batch.steps)
                saved = False
                if ckpt.enabled and crossed_save_point(
                        global_step, batch.steps, ckpt.save_every):
                    # Never checkpoint unvalidated state: force this
                    # window's losses (rare — only at the save cadence)
                    # so a rollback target is always finite AND always a
                    # fusion boundary.  Re-armed with a fresh deadline
                    # first: the materialization blocks on the device,
                    # and a hang HERE must fire the watchdog too.
                    watchdog.arm(global_step, scale=batch.steps)
                    guard.check_vector(*pending)
                    if global_step % ckpt.save_every == 0:
                        saved = ckpt.maybe_save(
                            global_step,
                            (state.params, state.opt_state, state.step))
                    else:
                        # Window boundary just past the cadence point.
                        ckpt.save(global_step,
                                  (state.params, state.opt_state,
                                   state.step))
                        saved = True
                watchdog.disarm()
                if tuner is not None:
                    tuner.on_window()
                if preemption_requested():
                    if ckpt.enabled and not saved:
                        ckpt.save(global_step,
                                  (state.params, state.opt_state,
                                   state.step))
                    ckpt.flush()
                    raise TrainPreempted("two_tower", global_step,
                                         ckpt.enabled)
        probe.finish()
        if pending is not None:
            guard.check_vector(*pending)
        guard.check_params(state.params, global_step)
        ckpt.complete()
    finally:
        # Close on EVERY path: a rollback re-entry reopens the directory
        # and must not race this attempt's in-flight async saves.
        watchdog.stop()
        ckpt.close()
    return state


def eval_loss(params: Dict, user_ids, item_ids, cfg: TwoTowerConfig) -> float:
    """In-batch sampled-softmax loss of ``params`` on one interaction
    sample — the warm-start regression gate's comparable scalar (same
    sample, same temperature, before vs after continuation)."""
    u = jnp.asarray(np.asarray(user_ids, np.int32))
    i = jnp.asarray(np.asarray(item_ids, np.int32))
    w = jnp.ones(u.shape[0], jnp.float32)
    return float(_loss(params, u, i, w, cfg.temperature))


def encode_users(params: Dict, user_ids: jax.Array) -> jax.Array:
    return _forward_users(params, user_ids)


def encode_items(params: Dict, item_ids: jax.Array) -> jax.Array:
    return _forward_items(params, item_ids)


def retrieve(params: Dict, user_ids: jax.Array, n_items: int, k: int,
             *, chunk: Optional[int] = None) -> Tuple[jax.Array, jax.Array]:
    """Top-k MIPS over all item embeddings (train-side eval utility —
    serving goes through :mod:`predictionio_tpu.retrieval`).

    With ``chunk`` the scan rides :func:`ops.pallas_kernels.fused_topk`:
    on TPU the fused Pallas kernel scores corpus tiles in VMEM and never
    materializes the [B, N] score block; elsewhere it falls back to the
    bounded-memory ``chunked_top_k`` scan (which now auto-pads ragged
    tails, so any ``n_items`` works).
    """
    from predictionio_tpu.ops.pallas_kernels import fused_topk

    q = _forward_users(params, user_ids)
    all_items = _forward_items(params, jnp.arange(n_items))
    if chunk:
        return fused_topk(q, all_items, k, chunk=chunk)
    return top_k_scores(q, all_items, k)
