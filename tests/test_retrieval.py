"""Retrieval subsystem (ISSUE 8): rung parity, IVF recall, generation
atomicity.

Parity suite pins sharded-exact ≡ single-device exact (same id set,
scores within fp tolerance) across shard counts, k ≥ per-shard rows, and
tail-padded corpora; IVF holds recall@10 ≥ 0.95 on a synthetic clustered
corpus while scanning < 25% of candidates; the exact fallback below
``PIO_IVF_MIN_ITEMS`` is contract, not accident; and a server-level
reload/rollback test proves index+model swap atomically (a generation-N
index can never serve next to generation-M vectors — the fingerprint
tripwire drops it loudly).  CPU-only: the 8-device virtual mesh from
conftest gives real sharding semantics.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops.topk import chunked_top_k, top_k_scores
from predictionio_tpu.parallel.mesh import make_mesh
from predictionio_tpu.retrieval import (
    K_MENU,
    IVFIndex,
    Retriever,
    build_ivf,
    build_train_index,
    cached_retriever,
    corpus_fingerprint,
    iter_hits,
    menu_k,
)
from predictionio_tpu.retrieval.ivf import (
    ivf_build_config,
    search_ivf_device,
    search_ivf_host,
)


def _corpus(n=256, d=16, seed=0):
    rng = np.random.default_rng(seed)
    items = rng.normal(size=(n, d)).astype(np.float32)
    queries = rng.normal(size=(4, d)).astype(np.float32)
    return queries, items


def _clustered_corpus(n=4000, d=16, n_clusters=40, seed=0):
    """Well-separated direction clusters + queries near members — the
    IVF design target (normalized two-tower-style corpus)."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(n_clusters, d)).astype(np.float32)
    centers /= np.linalg.norm(centers, axis=1, keepdims=True)
    assign = rng.integers(0, n_clusters, n)
    items = centers[assign] + 0.15 * rng.normal(size=(n, d)).astype(
        np.float32)
    items /= np.linalg.norm(items, axis=1, keepdims=True)
    q_src = rng.integers(0, n, 64)
    queries = items[q_src] + 0.05 * rng.normal(size=(64, d)).astype(
        np.float32)
    queries /= np.linalg.norm(queries, axis=1, keepdims=True)
    return queries.astype(np.float32), items.astype(np.float32)


def _exact_ids(queries, items, k):
    s = queries @ items.T
    return np.argsort(-s, axis=1, kind="stable")[:, :k]


# -- facade routing ----------------------------------------------------------


class TestRouting:
    def test_menu_k_pads_to_menu_and_clamps(self):
        assert menu_k(3, 10_000) == 10
        assert menu_k(10, 10_000) == 10
        assert menu_k(11, 10_000) == 100
        assert menu_k(5000, 10_000) == 5000  # past the menu: as asked
        assert menu_k(100, 7) == 7           # never beyond the corpus
        assert K_MENU == (1, 10, 100, 1000)

    def test_small_work_routes_host(self, monkeypatch):
        q, items = _corpus()
        r = Retriever(items, name="t-host")
        assert r.plan(1, 10).rung == "host"

    def test_large_work_routes_device(self, monkeypatch):
        q, items = _corpus()
        monkeypatch.setenv("PIO_SERVE_HOST_MACS", "10")
        r = Retriever(items, name="t-dev")
        assert r.plan(4, 10).rung == "device"

    def test_chunk_threshold_routes_chunked(self, monkeypatch):
        q, items = _corpus()
        monkeypatch.setenv("PIO_SERVE_HOST_MACS", "10")
        monkeypatch.setenv("PIO_SERVE_CHUNK_ABOVE", "100")
        r = Retriever(items, name="t-chunk")
        assert r.plan(4, 10).rung == "chunked"

    def test_forced_rung_env(self, monkeypatch):
        q, items = _corpus()
        monkeypatch.setenv("PIO_RETRIEVAL_RUNG", "device")
        r = Retriever(items, name="t-forced")
        assert r.plan(1, 10).rung == "device"

    def test_unrecognized_forced_rung_warns_and_autos(
            self, monkeypatch, caplog):
        """A typo'd forcing must degrade as loudly as an impossible one —
        a benchmark must not silently measure auto routing."""
        import logging

        q, items = _corpus()
        monkeypatch.setenv("PIO_RETRIEVAL_RUNG", "shard")  # typo
        r = Retriever(items, name="t-typo")
        with caplog.at_level(logging.WARNING,
                             logger="predictionio_tpu.retrieval"):
            p = r.plan(1, 10)
        assert p.rung == "host"
        assert any("PIO_RETRIEVAL_RUNG" in rec.getMessage()
                   for rec in caplog.records)

    def test_device_rung_padding_mask_staged_once(self):
        """The n_items<n padding mask is request-invariant — staged as a
        [N] device row once, never rebuilt [B, N] host-side per request."""
        from predictionio_tpu.retrieval.exact import exact_device

        q, items = _corpus(n=120)
        padded = np.concatenate(
            [items, np.ones((8, items.shape[1]), np.float32) * 100])
        cache = {}
        s1, i1 = exact_device(q, jnp.asarray(padded), 120, 10,
                              jit_cache=cache)
        assert ("pad_row", 128, 120) in cache
        assert (i1 < 120).all()  # padding rows never surface
        want = _exact_ids(q, items, 10)
        np.testing.assert_array_equal(np.sort(i1, axis=1),
                                      np.sort(want, axis=1))
        # padding + per-request exclude compose on device
        excl = np.zeros((len(q), 120), dtype=bool)
        excl[np.arange(len(q)), want[:, 0]] = True
        _, i2 = exact_device(q, jnp.asarray(padded), 120, 10,
                             jit_cache=cache, exclude=excl)
        assert (i2 < 120).all()
        for row in range(len(q)):
            assert want[row, 0] not in i2[row]

    def test_forced_sharded_without_mesh_degrades_to_device(
            self, monkeypatch):
        q, items = _corpus()
        monkeypatch.setenv("PIO_RETRIEVAL_RUNG", "sharded")
        r = Retriever(items, name="t-noshard")
        assert r.plan(1, 10).rung == "device"

    def test_exclude_pins_exact_even_with_ivf(self, monkeypatch):
        monkeypatch.setenv("PIO_IVF_MIN_ITEMS", "100")
        q, items = _clustered_corpus(n=600, n_clusters=10)
        idx = build_ivf(items, nlist=8, force=True)
        r = Retriever(items, ivf=idx, name="t-excl")
        assert r.plan(1, 10).rung == "ivf"
        assert r.plan(1, 10, has_exclude=True).rung == "host"

    def test_forced_nonexact_rung_with_exclude_serves_exact(
            self, monkeypatch):
        """A forced sharded/ivf rung takes no per-request mask — the
        exclusion must win over the forcing (a blacklisted item may
        never be returned).  A forced chunked rung carries the mask
        through the scan, so it keeps the forcing AND the exclusion."""
        monkeypatch.setenv("PIO_IVF_MIN_ITEMS", "100")
        q, items = _clustered_corpus(n=600, n_clusters=10)
        idx = build_ivf(items, nlist=8, force=True)
        excl = np.zeros((1, len(items)), dtype=bool)
        excl[0, _exact_ids(q[:1], items, 1)[0, 0]] = True
        for rung in ("sharded", "ivf"):
            monkeypatch.setenv("PIO_RETRIEVAL_RUNG", rung)
            r = Retriever(items, ivf=idx, name=f"t-exclforce-{rung}")
            assert r.plan(1, 10, has_exclude=True).rung in ("host",
                                                            "device")
            _, ids, info = r.topk(q[:1], 10, exclude=excl)
            assert info["rung"] in ("host", "device")
            assert excl[0, ids[0]].sum() == 0
        monkeypatch.setenv("PIO_RETRIEVAL_RUNG", "chunked")
        r = Retriever(items, ivf=idx, name="t-exclforce-chunked")
        assert r.plan(1, 10, has_exclude=True).rung == "chunked"
        _, ids, info = r.topk(q[:1], 10, exclude=excl)
        assert info["rung"] == "chunked"
        assert excl[0, ids[0]].sum() == 0

    def test_exclude_above_chunk_threshold_rides_chunked(
            self, monkeypatch):
        """Exclude queries past PIO_SERVE_CHUNK_ABOVE must not fall onto
        the dense device rung (a fresh [B, N] mask upload + [B, N] score
        block per request) — the mask rides the bounded-memory scan."""
        monkeypatch.setenv("PIO_SERVE_HOST_MACS", "1")
        monkeypatch.setenv("PIO_SERVE_CHUNK_ABOVE", "100")
        q, items = _corpus(n=300)
        q = q[:2]
        excl = np.zeros((2, len(items)), dtype=bool)
        want = _exact_ids(q, items, 1)
        excl[np.arange(2), want[:, 0]] = True
        r = Retriever(items, name="t-excl-chunk")
        assert r.plan(2, 10, has_exclude=True).rung == "chunked"
        _, ids, info = r.topk(q, 10, exclude=excl)
        assert info["rung"] == "chunked"
        for row in range(2):
            assert want[row, 0] not in ids[row]

    def test_device_exclude_with_non_pow2_batch(self, monkeypatch):
        """The pow2 batch pad must pad the exclude mask too — B=3 with a
        mask used to crash the device rung on a shape mismatch."""
        monkeypatch.setenv("PIO_RETRIEVAL_RUNG", "device")
        q, items = _corpus(n=300)
        q = q[:3]
        excl = np.zeros((3, len(items)), dtype=bool)
        want = _exact_ids(q, items, 1)
        excl[np.arange(3), want[:, 0]] = True
        r = Retriever(items, name="t-excl-pow2")
        _, ids, info = r.topk(q, 10, exclude=excl)
        assert info["rung"] == "device"
        assert ids.shape[0] == 3
        for row in range(3):
            assert want[row, 0] not in ids[row]

    def test_all_rungs_agree_on_ids(self, monkeypatch):
        """Every forced exact rung returns the SAME top-k id set."""
        q, items = _corpus(n=300)
        want = _exact_ids(q, items, 10)
        for rung in ("host", "device", "chunked"):
            monkeypatch.setenv("PIO_RETRIEVAL_RUNG", rung)
            r = Retriever(items, name=f"t-agree-{rung}")
            scores, ids, info = r.topk(q, 10)
            assert info["rung"] == rung
            np.testing.assert_array_equal(np.sort(ids, axis=1),
                                          np.sort(want, axis=1),
                                          err_msg=rung)

    def test_chunked_pallas_call_observes_the_kernels_round_count(
            self, monkeypatch):
        """One exact_chunked call on the Pallas path (interpreted here)
        observes pio_topk_fold_rounds_per_tile once, with the number the
        kernel itself returns over the tiles it scanned; the XLA scan
        (an exclude mask, or no Pallas) observes nothing; fused_topk's
        callers still get two arrays."""
        from predictionio_tpu.obs import get_registry
        from predictionio_tpu.ops import pallas_kernels as pk
        from predictionio_tpu.retrieval import exact

        q, items = _corpus(n=2500)
        dev = jnp.asarray(items)
        want = _exact_ids(q, items, 10)
        series = "pio_topk_fold_rounds_per_tile"

        s, i = exact.exact_chunked(q, dev, 2500, 10, jit_cache={})
        assert get_registry().get(series) is None      # XLA scan on the CPU

        monkeypatch.setattr(exact, "pallas_supported", lambda: True)
        monkeypatch.setattr(
            exact, "fused_topk_pallas",
            lambda *a, **kw: pk.fused_topk_pallas(*a, **kw, interpret=True))
        s, i = exact.exact_chunked(q, dev, 2500, 10, jit_cache={})
        np.testing.assert_array_equal(np.sort(i, 1), np.sort(want, 1))
        hist = get_registry().get(series)
        assert hist.count(rung="chunked") == 1
        _, _, rounds = pk.fused_topk_pallas(jnp.asarray(q), dev, 10,
                                            n_valid=2500, interpret=True)
        assert pk.fused_topk_tiles(2500) == 3
        assert hist.sum(rung="chunked") == pytest.approx(int(rounds) / 3)
        assert 10 / 3 <= hist.sum(rung="chunked") <= 10

        excl = np.zeros((len(q), 2500), bool)
        exact.exact_chunked(q, dev, 2500, 10, jit_cache={}, exclude=excl)
        assert hist.count(rung="chunked") == 1
        two = pk.fused_topk(jnp.asarray(q), dev, 10, use_pallas=True)
        assert len(two) == 2
        np.testing.assert_array_equal(np.asarray(two[1]), i)


# -- sharded-exact ≡ single-device parity (tentpole acceptance) --------------


class TestShardedParity:
    def _sharded_retriever(self, items, n_shards, monkeypatch,
                           n_items=None):
        monkeypatch.setenv("PIO_SERVE_SHARD_ABOVE", "1")
        # Force the work past the host fast path so routing picks the
        # sharded rung for these small parity corpora.
        monkeypatch.setenv("PIO_SERVE_HOST_MACS", "1")
        mesh = make_mesh({"data": n_shards})
        r = Retriever(items, n_items=n_items, name=f"t-sh{n_shards}")
        assert r.maybe_shard(mesh)
        assert r.sharded
        return r

    @pytest.mark.parametrize("n_shards", [2, 4, 8])
    def test_parity_across_shard_counts(self, n_shards, monkeypatch):
        q, items = _corpus(n=320)
        r = self._sharded_retriever(items, n_shards, monkeypatch)
        assert r.plan(4, 10).rung == "sharded"
        scores, ids, info = r.topk(q, 10)
        want_ids = _exact_ids(q, items, 10)
        want_s = np.take_along_axis(q @ items.T, want_ids, axis=1)
        np.testing.assert_array_equal(np.sort(ids, axis=1),
                                      np.sort(want_ids, axis=1))
        np.testing.assert_allclose(scores, want_s, rtol=1e-5, atol=1e-5)

    def test_parity_k_geq_per_shard_rows(self, monkeypatch):
        """k greater than any shard's row count: the local top-k takes
        the whole shard and the merge must still be globally exact."""
        q, items = _corpus(n=32)
        r = self._sharded_retriever(items, 8, monkeypatch)  # 4 rows/shard
        scores, ids, _ = r.topk(q, 8)  # menu pads k to 10; slice num=8
        np.testing.assert_array_equal(np.sort(ids[:, :8], axis=1),
                                      np.sort(_exact_ids(q, items, 8),
                                              axis=1))

    def test_parity_tail_padded_corpus(self, monkeypatch):
        """A corpus that does not divide the mesh is host-padded by
        maybe_shard; the padding rows must never appear in results."""
        q, items = _corpus(n=301)  # 301 % 8 != 0
        r = self._sharded_retriever(items, 8, monkeypatch)
        assert r.vecs.shape[0] == 304  # padded to the mesh
        scores, ids, _ = r.topk(q, 20)  # menu pads k to 100; slice 20
        assert int(ids.max()) < 301
        np.testing.assert_array_equal(np.sort(ids[:, :20], axis=1),
                                      np.sort(_exact_ids(q, items, 20),
                                              axis=1))

    def test_below_threshold_does_not_shard(self, monkeypatch):
        monkeypatch.setenv("PIO_SERVE_SHARD_ABOVE", "1000000")
        q, items = _corpus()
        r = Retriever(items, name="t-noshard")
        assert not r.maybe_shard(make_mesh({"data": 2}))
        assert not r.sharded


# -- chunked auto-pad (satellite: no more n % chunk == 0 assert) -------------


class TestChunkedAutoPad:
    @pytest.mark.parametrize("n", [100, 128, 129, 255])
    def test_ragged_tail_matches_dense(self, n):
        q, items = _corpus(n=n)
        s1, i1 = top_k_scores(jnp.asarray(q), jnp.asarray(items), 7)
        s2, i2 = chunked_top_k(jnp.asarray(q), jnp.asarray(items), 7,
                               chunk=64)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                                   rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))

    def test_biases_ride_the_tail_chunk(self):
        q, items = _corpus(n=150)
        bias = np.linspace(0, 3, 150).astype(np.float32)
        s1, i1 = top_k_scores(jnp.asarray(q), jnp.asarray(items), 5,
                              biases=jnp.asarray(bias))
        s2, i2 = chunked_top_k(jnp.asarray(q), jnp.asarray(items), 5,
                               chunk=64, biases=jnp.asarray(bias))
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                                   rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))

    def test_exclude_mask_sliced_per_chunk(self):
        q, items = _corpus(n=130)
        top1 = np.argmax(q @ items.T, axis=1)
        excl = np.zeros((4, 130), dtype=bool)
        excl[np.arange(4), top1] = True
        _, ids = chunked_top_k(jnp.asarray(q), jnp.asarray(items), 5,
                               chunk=64, exclude=jnp.asarray(excl))
        ids = np.asarray(ids)
        assert not any(top1[b] in ids[b] for b in range(4))

    def test_n_valid_masks_padding_rows(self):
        q, items = _corpus(n=192)
        items[150:] = 100.0  # poison rows that MUST be masked
        _, ids = chunked_top_k(jnp.asarray(q), jnp.asarray(items), 9,
                               chunk=64, n_valid=150)
        assert int(np.asarray(ids).max()) < 150
        # single-dispatch small-corpus path folds n_valid the same way
        _, ids2 = chunked_top_k(jnp.asarray(q), jnp.asarray(items), 9,
                                chunk=256, n_valid=150)
        np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids2))


# -- IVF ---------------------------------------------------------------------


class TestIVF:
    def test_recall_and_sublinear_scan(self, monkeypatch):
        """Acceptance: recall@10 ≥ 0.95 at default nprobe while scanning
        < 25% of candidates on the synthetic clustered corpus."""
        monkeypatch.delenv("PIO_IVF_NPROBE", raising=False)
        q, items = _clustered_corpus()
        idx = build_ivf(items, force=True)
        r = Retriever(items, ivf=idx, name="t-recall")
        p = r.plan(len(q), 10)
        assert p.rung == "ivf"
        scores, ids, info = r.topk(q, 10)
        want = _exact_ids(q, items, 10)
        hit = sum(len(set(ids[b, :10]) & set(want[b])) for b in
                  range(len(q)))
        recall = hit / want.size
        assert recall >= 0.95, f"recall@10={recall:.3f}"
        assert info["candidates"] < 0.25 * len(q) * len(items), info

    def test_host_and_device_search_agree(self):
        q, items = _clustered_corpus(n=1200, n_clusters=12)
        idx = build_ivf(items, nlist=12, force=True)
        s1, i1, c1 = search_ivf_host(idx, items, q, 10, nprobe=4)
        s2, i2, c2 = search_ivf_device(idx, jnp.asarray(items), q, 10,
                                       nprobe=4, jit_cache={})
        np.testing.assert_array_equal(np.sort(i1, axis=1),
                                      np.sort(i2, axis=1))
        np.testing.assert_allclose(np.sort(s1, axis=1),
                                   np.sort(s2, axis=1), rtol=1e-5,
                                   atol=1e-5)
        assert c1 == c2

    def test_exact_fallback_below_threshold(self, monkeypatch):
        """Below PIO_IVF_MIN_ITEMS no index is built — even with
        PIO_IVF=on the threshold is the contract."""
        monkeypatch.setenv("PIO_IVF", "on")
        monkeypatch.setenv("PIO_IVF_MIN_ITEMS", "1000")
        build, nlist, min_items = ivf_build_config(999)
        assert (build, min_items) == (False, 1000)
        assert build_train_index(np.ones((999, 4), np.float32),
                                 name="t") is None
        build, nlist, _ = ivf_build_config(1000)
        assert build and nlist == 32  # ~sqrt(N)

    def test_off_switch_never_builds(self, monkeypatch):
        monkeypatch.setenv("PIO_IVF", "off")
        monkeypatch.setenv("PIO_IVF_MIN_ITEMS", "1")
        assert build_train_index(np.ones((500, 4), np.float32),
                                 name="t") is None

    def test_nprobe_env_override_and_clamp(self, monkeypatch):
        q, items = _clustered_corpus(n=900, n_clusters=9)
        idx = build_ivf(items, nlist=9, force=True)
        monkeypatch.setenv("PIO_IVF_NPROBE", "3")
        assert idx.default_nprobe() == 3
        monkeypatch.setenv("PIO_IVF_NPROBE", "999")
        assert idx.default_nprobe() == 9  # clamped to nlist
        monkeypatch.delenv("PIO_IVF_NPROBE")
        assert idx.default_nprobe() == 2  # ~nlist/8, >= 1

    def test_plan_widens_nprobe_until_k_reachable(self, monkeypatch):
        """Static-shape guard: probed lists must cover k candidates."""
        monkeypatch.setenv("PIO_IVF_NPROBE", "1")
        q, items = _clustered_corpus(n=800, n_clusters=8)
        idx = build_ivf(items, nlist=8, force=True)
        r = Retriever(items, ivf=idx, name="t-widen")
        k = idx.pad_len + 1  # one probed list can never cover k
        p = r.plan(1, k)
        assert p.rung == "ivf" and p.nprobe >= 2

    def test_widening_uses_true_lengths_not_pad_len(self):
        """Skewed clusters: one giant list sets pad_len while typical
        lists hold a couple of items — nprobe·pad_len ≥ k is satisfied
        at nprobe=1 yet the probed lists can hold < k real candidates.
        The bound must use TRUE list lengths (worst case: the query
        lands on the shortest lists)."""
        idx = IVFIndex(centroids=np.zeros((4, 8), np.float32),
                       lists=np.full((4, 50), -1, np.int32),
                       list_lengths=np.array([50, 2, 2, 2], np.int32),
                       n_items=56, dim=8, nlist=4, pad_len=50,
                       fingerprint="x")
        assert idx.min_nprobe_for(2) == 1
        assert idx.min_nprobe_for(6) == 3   # 2+2+2 covers 6
        assert idx.min_nprobe_for(10) == 4  # needs the giant list too
        assert idx.min_nprobe_for(57) == 4  # > total: every list

    def test_ivf_device_constants_staged_once(self):
        """Centroids + padded lists are generation constants — staged on
        the retriever ONCE, never re-uploaded per request."""
        q, items = _clustered_corpus(n=1200, n_clusters=12)
        idx = build_ivf(items, nlist=12, force=True)
        r = Retriever(items, ivf=idx, name="t-staged")
        a1 = r.ivf_device_arrays()
        a2 = r.ivf_device_arrays()
        assert a1[0] is a2[0] and a1[1] is a2[1]
        s1, i1, _ = search_ivf_host(idx, items, q[:4], 10, 4)
        _, i2, _ = search_ivf_device(idx, jnp.asarray(items), q[:4], 10,
                                     4, jit_cache={}, consts=a1)
        np.testing.assert_array_equal(np.sort(i1, axis=1),
                                      np.sort(i2, axis=1))

    def test_malformed_nlist_env_falls_back(self, monkeypatch):
        """A typo'd PIO_IVF_NLIST must not crash pio train after the
        expensive fit — fall back to the ~sqrt(N) default loudly."""
        monkeypatch.setenv("PIO_IVF", "on")
        monkeypatch.setenv("PIO_IVF_MIN_ITEMS", "1")
        monkeypatch.setenv("PIO_IVF_NLIST", "2e3")
        build, nlist, _ = ivf_build_config(1024)
        assert build and nlist == 32

    def test_norm_variant_corpus_requires_explicit_on(self, monkeypatch):
        """Raw ALS factors are a poor IVF fit (norm-variant corpus) —
        the ALS template's index builds only under an explicit
        PIO_IVF=on, never auto (the README's 'opt in knowingly')."""
        monkeypatch.setenv("PIO_IVF_MIN_ITEMS", "1")
        monkeypatch.delenv("PIO_IVF", raising=False)
        _, items = _clustered_corpus(n=300, n_clusters=3)
        assert build_train_index(items, name="als",
                                 require_explicit=True) is None
        monkeypatch.setenv("PIO_IVF", "on")
        assert build_train_index(items, name="als",
                                 require_explicit=True) is not None

    def test_seedless_build_is_deterministic(self, monkeypatch):
        """Templates with no configured seed still build the SAME index
        over the same data — recall characteristics and bench
        comparisons must not drift run-to-run."""
        monkeypatch.setenv("PIO_IVF_MIN_ITEMS", "1")
        _, items = _clustered_corpus(n=400, n_clusters=4)
        a = build_train_index(items, name="t")
        b = build_train_index(items, name="t")
        np.testing.assert_array_equal(a.centroids, b.centroids)
        np.testing.assert_array_equal(a.lists, b.lists)

    def test_rows_short_of_k_pad_with_sentinels(self):
        q, items = _corpus(n=40)
        idx = build_ivf(items, nlist=4, force=True)
        s, i, _ = search_ivf_host(idx, items, q[:1], 39, nprobe=1)
        assert (i[0] == -1).any()  # one probed list cannot reach k=39
        assert list(iter_hits(s[0], i[0], 39))  # sentinels skipped


# -- generation versioning (the tripwire) ------------------------------------


class TestGenerationAtomicity:
    def test_fingerprint_stable_across_roundtrip(self):
        _, items = _corpus()
        import pickle

        again = pickle.loads(pickle.dumps(items))
        assert corpus_fingerprint(items) == corpus_fingerprint(again)
        assert corpus_fingerprint(items) != corpus_fingerprint(items + 1)

    def test_mismatched_index_dropped_loudly(self, pio_home):
        """A generation-N index next to generation-N+1 vectors is
        dropped (exact serving continues, counter increments) — recall
        never silently collapses through a stale index."""
        from predictionio_tpu.obs import get_registry

        q, items_n = _clustered_corpus(n=600, n_clusters=6, seed=1)
        _, items_n1 = _clustered_corpus(n=600, n_clusters=6, seed=2)
        stale = build_ivf(items_n, nlist=6, force=True)
        r = Retriever(items_n1, ivf=stale, name="t-mixed")
        assert r.ivf_index() is None  # dropped at first validation
        scores, ids, info = r.topk(q, 10)
        assert info["rung"] != "ivf"
        np.testing.assert_array_equal(
            np.sort(ids, axis=1),
            np.sort(_exact_ids(q, items_n1, 10), axis=1))
        c = get_registry().counter("pio_retrieval_ivf_rejected_total",
                                   "", ("corpus",))
        assert c.value(corpus="t-mixed") == 1

    def test_matching_index_survives_validation(self):
        q, items = _clustered_corpus(n=600, n_clusters=6)
        idx = build_ivf(items, nlist=6, force=True)
        r = Retriever(items, ivf=idx, name="t-match")
        assert r.ivf_index() is idx

    def test_wrapper_pickle_carries_index(self, monkeypatch):
        """Model and index are ONE artifact: the pickle round-trip the
        generation swap moves keeps them consistent by construction."""
        import pickle

        from predictionio_tpu.data.event import BiMap
        from predictionio_tpu.templates.twotower.engine import (
            TwoTowerModelWrapper,
        )

        _, items = _clustered_corpus(n=600, n_clusters=6)
        idx_map = BiMap.string_int([f"i{j}" for j in range(len(items))])
        u_map = BiMap.string_int(["u0"])
        w = TwoTowerModelWrapper(
            user_vecs=np.ones((1, items.shape[1]), np.float32),
            item_vecs=items, user_index=u_map, item_index=idx_map,
            ivf=build_ivf(items, nlist=6, force=True))
        w2 = pickle.loads(pickle.dumps(w))
        assert w2.ivf is not None
        assert Retriever(w2.item_vecs, ivf=w2.ivf,
                         name="t-pickle").ivf_index() is w2.ivf


# -- server-level reload/rollback atomicity ----------------------------------


def _trained_ivf_server(storage, seed_rank):
    """ALS engine server with IVF forced on (tiny threshold)."""
    from predictionio_tpu.controller import EngineVariant, RuntimeContext
    from predictionio_tpu.data.event import DataMap, Event
    from predictionio_tpu.data.storage import App
    from predictionio_tpu.server import EngineServer
    from predictionio_tpu.templates.recommendation import engine
    from predictionio_tpu.workflow.core_workflow import run_train

    ctx = RuntimeContext.create(storage=storage)
    app_id = storage.get_apps().insert(App(id=None, name="ivfapp"))
    storage.get_events().init(app_id)
    rng = np.random.default_rng(7)
    storage.get_events().insert_batch(
        [Event(event="rate", entity_type="user", entity_id=f"u{u}",
               target_entity_type="item", target_entity_id=f"i{i}",
               properties=DataMap({"rating": float(r)}))
         for u, i, r in zip(rng.integers(0, 30, 600),
                            rng.integers(0, 64, 600),
                            rng.integers(1, 6, 600))], app_id)
    variant = EngineVariant.from_dict({
        "engineFactory": "predictionio_tpu.templates.recommendation:engine",
        "datasource": {"params": {"appName": "ivfapp"}},
        "algorithms": [{"name": "als",
                        "params": {"rank": seed_rank,
                                   "numIterations": 2}}],
    })
    eng = engine()
    run_train(eng, variant, ctx)
    srv = EngineServer(eng, variant, storage, host="127.0.0.1", port=0)
    return srv, eng, variant, ctx


def _serving_wrapper(srv):
    return srv._models[0]


def _assert_generation_consistent(wrapper):
    """The served index MUST fingerprint-match the served vectors."""
    idx = wrapper.retriever().ivf_index()
    assert idx is not None, "IVF index missing from the serving wrapper"
    host = wrapper.host_factors()[1]
    assert idx.fingerprint == corpus_fingerprint(host)
    return idx


def test_reload_and_rollback_swap_index_with_model(pio_home, monkeypatch):
    """ISSUE 8 acceptance: the staged-reload/canary/rollback path swaps
    index+model atomically — a rollback never serves generation-N
    vectors through a generation-N+1 index."""
    monkeypatch.setenv("PIO_IVF", "on")
    monkeypatch.setenv("PIO_IVF_MIN_ITEMS", "10")
    from predictionio_tpu.data.storage import get_storage
    from predictionio_tpu.workflow.core_workflow import run_train

    storage = get_storage()
    srv, eng, variant, ctx = _trained_ivf_server(storage, seed_rank=4)
    idx1 = _assert_generation_consistent(_serving_wrapper(srv))
    fp1 = idx1.fingerprint

    # Generation 2: more events → different factor matrix → a NEW
    # fingerprint.  The reload must carry its OWN index.
    from predictionio_tpu.data.event import DataMap, Event

    app_id = storage.get_apps().get_by_name("ivfapp").id
    rng = np.random.default_rng(11)
    storage.get_events().insert_batch(
        [Event(event="rate", entity_type="user", entity_id=f"u{u}",
               target_entity_type="item", target_entity_id=f"i{i}",
               properties=DataMap({"rating": float(r)}))
         for u, i, r in zip(rng.integers(0, 30, 200),
                            rng.integers(0, 64, 200),
                            rng.integers(1, 6, 200))], app_id)
    run_train(eng, variant, ctx)
    st, body = srv.handle("POST", "/reload", b"")
    assert st == 200 and body["generation"] == 2
    idx2 = _assert_generation_consistent(_serving_wrapper(srv))
    assert idx2.fingerprint != fp1

    # Rollback: generation 1's model AND generation 1's index return
    # together — never gen-1 vectors under the gen-2 index.
    st, body = srv.handle("POST", "/admin/rollback", b"")
    assert st == 200
    idx_back = _assert_generation_consistent(_serving_wrapper(srv))
    assert idx_back.fingerprint == fp1

    # And the rolled-back generation actually serves through its index.
    monkeypatch.setenv("PIO_RETRIEVAL_RUNG", "ivf")
    st, body = srv.handle("POST", "/queries.json",
                          b'{"user": "u1", "num": 3}')
    assert st == 200 and body["itemScores"]


def test_ivf_rides_train_and_serves(pio_home, monkeypatch):
    """End-to-end: `pio train` builds the index, serving routes the IVF
    rung, and the result ids match exact retrieval (tiny corpus →
    nprobe covers it)."""
    monkeypatch.setenv("PIO_IVF", "on")
    monkeypatch.setenv("PIO_IVF_MIN_ITEMS", "10")
    from predictionio_tpu.data.storage import get_storage

    storage = get_storage()
    srv, *_ = _trained_ivf_server(storage, seed_rank=4)
    w = _serving_wrapper(srv)
    _assert_generation_consistent(w)
    monkeypatch.setenv("PIO_RETRIEVAL_RUNG", "ivf")
    st, body = srv.handle("POST", "/queries.json",
                          b'{"user": "u2", "num": 5}')
    assert st == 200 and len(body["itemScores"]) == 5


# -- per-model retriever cache ----------------------------------------------


class TestRetrieverCache:
    def test_one_retriever_per_owner_dies_with_it(self):
        class Owner:
            pass

        _, items = _corpus()
        o = Owner()
        r1 = cached_retriever(o, lambda: Retriever(items, name="t-c1"))
        r2 = cached_retriever(o, lambda: Retriever(items, name="t-c2"))
        assert r1 is r2 and r1.name == "t-c1"
        import weakref

        ref = weakref.ref(r1)
        del r1, r2, o
        import gc

        gc.collect()
        assert ref() is None  # died with the generation

    def test_als_wrapper_retriever_does_not_pin_generation(self):
        """The ALS retriever's host_fn must hold the wrapper weakly: a
        strong capture would make the weak cache's value pin its own key
        and leak every swapped-out generation's factors."""
        import gc
        import weakref
        from types import SimpleNamespace

        from predictionio_tpu.data.event import BiMap
        from predictionio_tpu.templates.recommendation.engine import (
            ALSModelWrapper,
        )

        _, items = _corpus(n=64, d=8)
        wrapper = ALSModelWrapper(
            model=SimpleNamespace(user_factors=items[:8],
                                  item_factors=items),
            user_index=BiMap({f"u{j}": j for j in range(8)}),
            item_index=BiMap({f"i{j}": j for j in range(64)}))
        r = wrapper.retriever()
        # host_fn path works through the weakref while the wrapper lives
        assert r.host_vecs().shape == (64, 8)
        ref = weakref.ref(wrapper)
        del wrapper, r
        gc.collect()
        assert ref() is None  # generation NOT pinned by its retriever


# -- iter_hits ---------------------------------------------------------------


def test_iter_hits_skips_sentinels_and_honors_num():
    scores = np.array([5.0, -1e38, 3.0, 2.0], np.float32)
    ids = np.array([7, -1, 3, 9], np.int32)
    assert list(iter_hits(scores, ids, 2)) == [(7, 5.0), (3, 3.0)]
    assert list(iter_hits(scores, ids, 10)) == [(7, 5.0), (3, 3.0),
                                                (9, 2.0)]
