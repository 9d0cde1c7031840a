"""DLRM-style CTR ranking — sharded embedding tables + dense interaction.

Absent in the reference (SURVEY.md §2.2: new build target from BASELINE
config 5).  This is the EP-shaped component of the build (§2.4): the
categorical embedding tables dominate memory, so they are **row-sharded
over the ``expert`` mesh axis**, and lookups are exchanged with XLA
collectives over ICI.

Lookup design (``sharded_embedding_lookup``): all feature tables are
concatenated into one [ΣV, E] table, row-sharded.  Inside ``shard_map``:

1. every shard all-gathers the (tiny, int32) global index batch,
2. computes masked partial embeddings for the indices it owns
   (``idx ∈ [lo, hi)`` → ``table[idx - lo]``, else 0), and
3. ``psum_scatter`` returns each batch-shard its summed rows — exactly one
   owner contributes per index, so the sum IS the lookup.

Traffic: an all-gather of int32 indices + one reduce-scatter of the
embedding activations — both nearest-neighbor ICI patterns.  (The
request/reply ``all_to_all`` variant saves bandwidth at large expert
counts; this formulation is MXU-friendlier and exact.)

Model: bottom MLP over dense features, pairwise dot-product feature
interaction (the DLRM arch), top MLP → CTR logit.  bf16 matmuls, f32
master weights, optax adagrad (the DLRM-paper optimizer).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from predictionio_tpu.obs.runtime import get_compile_tracker
from predictionio_tpu.parallel.mesh import AXIS_EXPERT, put_sharded

__all__ = ["DLRMConfig", "DLRMState", "init_state", "train_step",
           "train_steps_fused", "train", "predict_proba",
           "sharded_embedding_lookup"]


@dataclasses.dataclass
class DLRMConfig:
    vocab_sizes: Tuple[int, ...]        # per categorical feature field
    n_dense: int                        # dense feature count
    embed_dim: int = 16
    bottom_mlp: Tuple[int, ...] = (64, 32)
    top_mlp: Tuple[int, ...] = (64, 32)
    learning_rate: float = 0.05
    batch_size: int = 512
    epochs: int = 2
    seed: int = 0

    def __post_init__(self):
        if self.bottom_mlp[-1] != self.embed_dim:
            raise ValueError(
                f"bottom_mlp[-1] ({self.bottom_mlp[-1]}) must equal embed_dim "
                f"({self.embed_dim}) — the dot interaction mixes them.")

    @property
    def total_vocab(self) -> int:
        return int(sum(self.vocab_sizes))

    @property
    def offsets(self) -> np.ndarray:
        """Row offset of each field's table in the concatenated table."""
        return np.cumsum([0, *self.vocab_sizes[:-1]]).astype(np.int32)


def _init_mlp(key, in_dim: int, dims: Sequence[int]) -> List[Dict]:
    layers = []
    all_dims = (in_dim, *dims)
    for a, b in zip(all_dims[:-1], all_dims[1:]):
        key, k = jax.random.split(key)
        layers.append({
            # max(a, 1): n_dense == 0 gives the bottom MLP a zero-width
            # input ([B,0]·[0,H] = 0 + bias) — legal, He scale undefined.
            "w": jax.random.normal(k, (a, b), jnp.float32)
            * (2.0 / max(a, 1)) ** 0.5,
            "b": jnp.zeros((b,), jnp.float32),
        })
    return layers


def _mlp(layers: List[Dict], x: jax.Array, final_relu: bool = True) -> jax.Array:
    h = x
    for i, layer in enumerate(layers):
        h = jnp.einsum("bd,dh->bh", h.astype(jnp.bfloat16),
                       layer["w"].astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32) + layer["b"]
        if final_relu or i < len(layers) - 1:
            h = jax.nn.relu(h)
    return h


def init_params(cfg: DLRMConfig) -> Dict:
    key = jax.random.PRNGKey(cfg.seed)
    ke, kb, kt = jax.random.split(key, 3)
    n_fields = len(cfg.vocab_sizes)
    # Interaction: pairwise dots among (n_fields + 1) vectors (emb + bottom).
    n_vec = n_fields + 1
    inter_dim = n_vec * (n_vec - 1) // 2 + cfg.bottom_mlp[-1]
    return {
        "embed": jax.random.normal(ke, (cfg.total_vocab, cfg.embed_dim),
                                   jnp.float32) * (cfg.embed_dim ** -0.5),
        "bottom": _init_mlp(kb, cfg.n_dense, (*cfg.bottom_mlp[:-1],
                                              cfg.bottom_mlp[-1])),
        "top": _init_mlp(kt, inter_dim, (*cfg.top_mlp, 1)),
    }


def param_shardings(cfg: DLRMConfig, mesh: Mesh):
    rep = NamedSharding(mesh, P())
    return {
        "embed": NamedSharding(mesh, P(AXIS_EXPERT, None)),
        "bottom": [jax.tree.map(lambda _: rep, l)
                   for l in init_params(cfg)["bottom"]],
        "top": [jax.tree.map(lambda _: rep, l)
                for l in init_params(cfg)["top"]],
    }


# -- the EP lookup ----------------------------------------------------------

def sharded_embedding_lookup(
    mesh: Mesh,
    table: jax.Array,     # [V, E] row-sharded over AXIS_EXPERT
    indices: jax.Array,   # [B, F] int32 global rows, batch-sharded over AXIS_EXPERT
) -> jax.Array:           # [B, F, E] batch-sharded
    """Row-sharded table lookup via all_gather(idx) + psum_scatter(rows)."""
    n_shards = mesh.shape[AXIS_EXPERT]
    v = table.shape[0]
    assert v % n_shards == 0, f"pad vocab ({v}) to a multiple of {n_shards}"
    rows_per = v // n_shards

    def local(tab, idx):  # tab: [V/S, E]; idx: [B/S, F]
        shard = jax.lax.axis_index(AXIS_EXPERT)
        idx_all = jax.lax.all_gather(idx, AXIS_EXPERT, axis=0,
                                     tiled=True)          # [B, F]
        rel = idx_all - shard * rows_per
        mine = (rel >= 0) & (rel < rows_per)
        safe = jnp.clip(rel, 0, rows_per - 1)
        part = jnp.where(mine[..., None], tab[safe], 0.0)  # [B, F, E]
        return jax.lax.psum_scatter(part, AXIS_EXPERT, scatter_dimension=0,
                                    tiled=True)            # [B/S, F, E]

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(AXIS_EXPERT, None), P(AXIS_EXPERT, None)),
        out_specs=P(AXIS_EXPERT, None, None),
    )(table, indices)


def _interact(emb: jax.Array, bottom_out: jax.Array) -> jax.Array:
    """DLRM pairwise-dot interaction: [B,F,E] x [B,E] → [B, F+1 choose 2 + D]."""
    vecs = jnp.concatenate([emb, bottom_out[:, None, :]], axis=1)  # [B,F+1,E]
    prods = jnp.einsum("bfe,bge->bfg", vecs, vecs,
                       preferred_element_type=jnp.float32)
    n = vecs.shape[1]
    iu, ju = jnp.triu_indices(n, k=1)
    flat = prods[:, iu, ju]                                        # [B, nC2]
    return jnp.concatenate([flat, bottom_out], axis=1)


def _forward(params: Dict, dense: jax.Array, cat: jax.Array,
             mesh: Optional[Mesh]) -> jax.Array:
    if mesh is not None and mesh.shape.get(AXIS_EXPERT, 1) > 1:
        emb = sharded_embedding_lookup(mesh, params["embed"], cat)
    else:
        emb = params["embed"][cat]                                 # [B, F, E]
    bottom_out = _mlp(params["bottom"], dense)                     # [B, D]
    z = _interact(emb, bottom_out)
    logit = _mlp(params["top"], z, final_relu=False)               # [B, 1]
    return logit[:, 0]


def _loss(params, dense, cat, labels, weights, mesh):
    logits = _forward(params, dense, cat, mesh)
    losses = optax.sigmoid_binary_cross_entropy(logits, labels)
    return jnp.sum(losses * weights) / jnp.maximum(jnp.sum(weights), 1.0)


@dataclasses.dataclass
class DLRMState:
    params: Dict
    opt_state: Any
    step: jax.Array


def _tx(cfg: DLRMConfig):
    return optax.adagrad(cfg.learning_rate)


def init_state(cfg: DLRMConfig, mesh: Optional[Mesh] = None) -> DLRMState:
    params = init_params(cfg)
    if mesh is not None:
        params = jax.tree_util.tree_map(
            lambda p, s_: put_sharded(p, mesh, s_),
            params, param_shardings(cfg, mesh))
    return DLRMState(params=params, opt_state=_tx(cfg).init(params),
                     step=jnp.zeros((), jnp.int32))


class _StepKey:
    """Static-arg wrapper for (cfg, mesh) — hashed by compile-relevant bits."""

    def __init__(self, cfg: DLRMConfig, mesh: Optional[Mesh]):
        self.cfg = cfg
        self.mesh = mesh
        self._key = (cfg.learning_rate, cfg.vocab_sizes, cfg.embed_dim,
                     cfg.bottom_mlp, cfg.top_mlp,
                     tuple(sorted(mesh.shape.items())) if mesh else None)

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, _StepKey) and self._key == other._key


def _step_math(state_tuple, dense, cat, labels, weights, key: _StepKey):
    """One optimizer step's pure math — shared VERBATIM by the per-step
    jit and the K-fused ``lax.scan`` body so fused training is the same
    traced computation (tests pin K=1 vs K>1 bitwise on CPU)."""
    params, opt_state, step = state_tuple
    loss, grads = jax.value_and_grad(_loss)(params, dense, cat, labels,
                                            weights, key.mesh)
    updates, opt_state = _tx(key.cfg).update(grads, opt_state, params)
    params = optax.apply_updates(params, updates)
    return (params, opt_state, step + 1), loss


# Batch tensors donated alongside the carried state (see two_tower): the
# prefetched pipeline stages fresh buffers per step, so donation bounds
# steady-state device memory at (prefetch depth + 1) batches.  CPU warns
# the donation was unusable — expected there (pyproject filters it for
# the test suite; where donation is real the warning stays audible).
_train_step_impl = functools.partial(
    jax.jit, static_argnames=("key",), donate_argnums=(0, 1, 2, 3, 4))(
        _step_math)


# K-step fused dispatch (ISSUE 7, see two_tower): ONE lax.scan program
# runs K optimizer steps over a K-stacked superbatch, donating state and
# the whole superbatch; returns the per-step loss vector [K] the
# divergence guard checks at the fusion boundary.
@functools.partial(jax.jit, static_argnames=("key",),
                   donate_argnums=(0, 1, 2, 3, 4))
def _fused_steps_impl(state_tuple, dense, cat, labels, weights,
                      key: _StepKey):
    def body(carry, batch):
        d, c, y, w = batch
        return _step_math(carry, d, c, y, w, key)

    return jax.lax.scan(body, state_tuple, (dense, cat, labels, weights))


# Compile tracking (obs.runtime): see two_tower — the fused entry point
# tracks under its own name so fusion-depth changes read as named
# compiles, not mystery churn.
_tracked_train_step = get_compile_tracker().wrap(
    "dlrm.train_step", _train_step_impl)
_tracked_fused_steps = get_compile_tracker().wrap(
    "dlrm.train_steps_fused", _fused_steps_impl)


def train_step(state: DLRMState, dense, cat, labels, weights,
               cfg: DLRMConfig, mesh: Optional[Mesh] = None):
    """One optimizer step.  ``state`` AND the batch tensors are donated:
    on donation-capable backends (TPU/GPU) the inputs are consumed — pass
    fresh device buffers per call (as the prefetched train loop does),
    not arrays you reuse afterwards."""
    (p, o, s), loss = _tracked_train_step(
        (state.params, state.opt_state, state.step),
        dense, cat, labels, weights, _StepKey(cfg, mesh))
    return DLRMState(params=p, opt_state=o, step=s), loss


def train_steps_fused(state: DLRMState, dense, cat, labels, weights,
                      cfg: DLRMConfig, mesh: Optional[Mesh] = None):
    """K fused optimizer steps in ONE XLA dispatch.

    Batch tensors carry a leading scan axis ([K, B, ...], staged by the
    prefetcher's superbatch assembly); state and the whole superbatch
    are donated.  Returns the carried state and the per-step loss vector
    [K].  The resulting model state is bitwise-equal to K sequential
    :func:`train_step` calls on the same batches (test-pinned on CPU;
    the observability loss scalars may sit 1 ulp off standalone
    dispatches — XLA fuses a rolled scan body's scalar output path
    differently)."""
    (p, o, s), losses = _tracked_fused_steps(
        (state.params, state.opt_state, state.step),
        dense, cat, labels, weights, _StepKey(cfg, mesh))
    return DLRMState(params=p, opt_state=o, step=s), losses


def train(
    dense: np.ndarray,      # [N, n_dense] float
    cat: np.ndarray,        # [N, F] int — PER-FIELD indices (offsets applied here)
    labels: np.ndarray,     # [N] {0,1}
    cfg: DLRMConfig,
    mesh: Optional[Mesh] = None,
    *,
    checkpoint_dir=None,
    save_every: int = 0,
    data_source: str = "auto",
    fuse_steps=None,
    warm_state: Optional[DLRMState] = None,
) -> DLRMState:
    """Minibatch CTR training.

    ``warm_state`` (ISSUE 10): continue from an existing state (the
    previous generation's) on a delta window instead of a fresh init —
    DLRM's hashed vocabularies are fixed-size, so no table growth is
    needed and any unseen entity already lands in a shared bucket.

    ``data_source`` mirrors two_tower.train: "feeder" streams batches
    from the native mmap cache (v3: any number of categorical columns —
    real CTR shapes have tens — the label on the value column, dense
    features on the extras columns); "numpy" is the host permutation;
    "auto" picks the feeder whenever the native library builds.
    ``checkpoint_dir`` + ``save_every`` give mid-training resume with
    deterministic per-(seed, epoch) batch order in both sources.

    Supervision mirrors two_tower.train: divergence rollback to the
    last-good checkpoint (bounded, then ``TrainDiverged``), SIGTERM
    preemption (``TrainPreempted`` after a final checkpoint), and the
    ``PIO_STEP_TIMEOUT_S`` step watchdog.

    ``fuse_steps`` mirrors two_tower.train: K optimizer steps fused into
    one ``lax.scan`` dispatch (bitwise-equal to K=1), ``"auto"`` grows
    depth until the HBM headroom guardrail pushes back; supervision
    moves to the fusion boundary (scaled watchdog deadline, per-step
    loss-vector divergence check, boundary-aligned checkpoints).
    """
    from predictionio_tpu.resilience.supervision import (
        DivergenceGuard,
        RollbackRequested,
    )

    # Without a checkpointer a "rollback" is a full deterministic retrain
    # that reproduces the same NaN — terminal immediately (max 0), same
    # policy as als.py.
    can_rollback = bool(checkpoint_dir) and save_every > 0
    guard = DivergenceGuard("dlrm",
                            max_rollbacks=None if can_rollback else 0)
    while True:
        try:
            return _train_attempt(dense, cat, labels, cfg, mesh,
                                  checkpoint_dir=checkpoint_dir,
                                  save_every=save_every,
                                  data_source=data_source, guard=guard,
                                  fuse_steps=fuse_steps,
                                  warm_state=warm_state)
        except RollbackRequested:
            continue  # re-enter: restore_step fast-forwards to last-good


def _train_attempt(
    dense: np.ndarray,
    cat: np.ndarray,
    labels: np.ndarray,
    cfg: DLRMConfig,
    mesh: Optional[Mesh],
    *,
    checkpoint_dir,
    save_every: int,
    data_source: str,
    guard,
    fuse_steps=None,
    warm_state: Optional[DLRMState] = None,
) -> DLRMState:
    from predictionio_tpu.resilience.supervision import (
        StepWatchdog,
        TrainPreempted,
        preemption_requested,
    )
    from predictionio_tpu.workflow.checkpoint import TrainCheckpointer

    n = len(labels)
    cat = np.asarray(cat)
    cat_global = (np.asarray(cat, np.int64) + cfg.offsets[None, :]).astype(np.int32)
    state = warm_state if warm_state is not None else init_state(cfg, mesh)
    total_steps = cfg.epochs * ((n + cfg.batch_size - 1) // cfg.batch_size)
    # Warm continuations fingerprint on the carried step: a crash-resume
    # checkpoint from a different base generation must not restore here.
    fp_extra = f"|warm@{int(jax.device_get(state.step))}" \
        if warm_state is not None else ""
    ckpt = TrainCheckpointer(checkpoint_dir or ".", save_every=save_every
                             if checkpoint_dir else 0,
                             fingerprint=f"dlrm|{cfg}|n={n}{fp_extra}")
    watchdog = StepWatchdog("dlrm", checkpoint_fn=ckpt.flush)
    start_step = ckpt.restore_step(
        (state.params, state.opt_state, state.step), total_steps=total_steps)
    if ckpt.restored_state is not None:
        p, o, s = ckpt.restored_state
        state = DLRMState(params=p, opt_state=o, step=s)
    bs = cfg.batch_size
    sh = NamedSharding(mesh, P(AXIS_EXPERT)) if mesh is not None else None

    def numpy_epochs():
        for epoch in range(cfg.epochs):
            order = np.random.default_rng(cfg.seed + epoch).permutation(n)
            for start in range(0, n, bs):
                sel = order[start:start + bs]
                yield (dense[sel], cat_global[sel],
                       labels[sel].astype(np.float32))

    def feeder_epochs():
        import tempfile

        from predictionio_tpu.native.feeder import EventFeeder, write_cache

        with tempfile.TemporaryDirectory(prefix="pio_dlrm_cache_") as d:
            # v3 cache: F categorical columns (any CTR shape), the label
            # on the value column, dense features on the extras columns.
            cache = write_cache(
                f"{d}/train.piof",
                cats=cat_global.astype(np.uint32),
                values=np.asarray(labels, np.float32),
                extras=(np.asarray(dense, np.float32)
                        if cfg.n_dense else None))
            with EventFeeder(cache, bs, seed=cfg.seed) as f:
                for _ in range(cfg.epochs):
                    for batch in f.epoch_cats():
                        c, y = batch[0], batch[1]
                        extras = (batch[2] if len(batch) > 2 else
                                  np.zeros((len(y), 0), np.float32))
                        yield extras, c.astype(np.int32), y

    use_feeder = data_source == "feeder"
    if data_source == "auto":
        from predictionio_tpu.native.build import load_library

        use_feeder = load_library("feeder") is not None
    # Overlapped input pipeline (ISSUE 5 / data/prefetch.py): padding +
    # dtype conversion + H2D run on a background prep thread so batch
    # N+1's transfer rides under batch N's device step (see two_tower).
    # K-step fusion (ISSUE 7 / data/fusion.py): superbatch staging + ONE
    # lax.scan dispatch per window, supervision at the window boundary.
    from predictionio_tpu.data.fusion import (
        FusionAutotuner,
        FusionPlan,
        crossed_save_point,
        fuse_steps_config,
        slot_steps,
    )
    from predictionio_tpu.data.prefetch import DevicePrefetcher
    from predictionio_tpu.obs import PipelineProbe

    n_fields = cat.shape[1]

    def prep(batch):
        # Prep-thread staging: identical layout/dtypes to the historical
        # inline path (tests pin bitwise equivalence on CPU).
        d, c, y = batch
        pad = bs - len(y)
        return (
            np.asarray(np.concatenate(
                [d, np.zeros((pad, cfg.n_dense), np.float32)]), np.float32),
            np.concatenate([c, np.zeros((pad, n_fields), np.int32)]),
            np.asarray(np.concatenate(
                [y, np.zeros(pad, np.float32)]), np.float32),
            np.concatenate([np.ones(len(y), np.float32),
                            np.zeros(pad, np.float32)]),
        )

    put = None
    fused_put = None
    if sh is not None:
        def put(arrays):
            return tuple(put_sharded(a, mesh, sh) for a in arrays)

        # Superbatch staging: batch axis moves to dim 1 under the scan
        # axis, so shard dim 1 and replicate the leading axis.
        fused_sh = NamedSharding(mesh, P(None, AXIS_EXPERT))

        def fused_put(arrays):
            return tuple(put_sharded(a, mesh, fused_sh) for a in arrays)

    k0, auto = fuse_steps_config(fuse_steps)
    plan = FusionPlan(k0)
    tuner = FusionAutotuner("dlrm", plan) if auto else None

    probe = PipelineProbe("dlrm")
    global_step = start_step
    pending = None  # (losses, slot steps) of the in-flight dispatch
    in_flight = 0  # raw steps covered by the in-flight dispatch
    try:
        with DevicePrefetcher(
                feeder_epochs() if use_feeder else numpy_epochs(),
                prep, put_fn=put, fused_put_fn=fused_put,
                skip_steps=start_step, fuse_plan=plan,
                model="dlrm") as pf:
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
                    # above — every slot checked at the fusion boundary.
                    guard.check_vector(*pending)
                if batch.k > 1:
                    state, losses = train_steps_fused(state, *batch.args,
                                                      cfg, mesh)
                else:
                    state, losses = train_step(state, *batch.args, cfg,
                                               mesh)
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
                    # Fresh watchdog deadline: the forced loss-vector
                    # check blocks on the device and a hang here must
                    # fire too.  Checkpoints land on fusion boundaries —
                    # never a NaN state, never mid-window.
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
                    raise TrainPreempted("dlrm", global_step, ckpt.enabled)
        probe.finish()
        if pending is not None:
            guard.check_vector(*pending)
        guard.check_params(state.params, global_step)
        ckpt.complete()
    finally:
        watchdog.stop()
        ckpt.close()
    return state


def predict_proba(state: DLRMState, dense: np.ndarray, cat: np.ndarray,
                  cfg: DLRMConfig, mesh: Optional[Mesh] = None) -> jax.Array:
    cat_global = (np.asarray(cat, np.int64) + cfg.offsets[None, :]).astype(np.int32)
    logits = _forward(state.params, jnp.asarray(dense, jnp.float32),
                      jnp.asarray(cat_global), mesh)
    return jax.nn.sigmoid(logits)
