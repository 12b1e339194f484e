"""The all-device analyzer hot path (ISSUE 9): the batched multi-seed
Pallas kernel, the lockstep device clustering rounds, the persistent
device row cache, and the jitted k-means — all validated against the
bit-exact numpy reference.

Contracts pinned here:

* ``multi_seed_rows`` (one Pallas call for all seeds) is **bitwise**
  equal to per-seed ``seed_rows`` calls on the same backend — batching
  must never change a value — and matches the float64 brute-force D²
  definition to the documented f32 Gram tolerance, including when the
  seed axis spans multiple kernel tiles;
* the device lockstep path (jax and pallas backends) produces the same
  partitions as the numpy host path across random shapes, trial counts
  and toggle widths — for ``cluster()``, ``cluster_batch`` and the
  empty-matrix/edge shapes;
* each unique seed is fetched from the backend **at most once per
  state** (device path) / once per lockstep round (host batched path):
  the fetch counters prove the memo actually memoizes;
* ``kmeans_1d`` on the jax backend reproduces the numpy reference
  exactly (same labels/centroids) across a sweep;
* (slow) every synthetic corpus entry's full verdict is identical under
  the accelerated backends.
"""
import numpy as np
import pytest

from repro.core import (AutoAnalyzer, IncrementalClusterState,
                        get_distance_backend)
from repro.core.clustering import kmeans_1d

jax = pytest.importorskip("jax")


def _brute_rows(W, idx):
    return np.array([[((W[p] - W[q]) ** 2).sum() for q in range(W.shape[0])]
                     for p in idx])


def _workload(m=40, n=6, seed=0):
    rng = np.random.default_rng(seed)
    W = 100.0 + rng.random((m, n))
    W[: m // 4] *= 7.0          # well-separated straggler block
    return W


# -- batched multi-seed kernel --------------------------------------------


class TestMultiSeedRows:
    @pytest.mark.parametrize("m,n,k", [(16, 1, 1), (40, 6, 5),
                                       (130, 17, 9), (513, 3, 12),
                                       (64, 130, 7)])
    @pytest.mark.parametrize("name", ["jax", "pallas"])
    def test_batched_equals_per_seed_bitwise(self, name, m, n, k):
        """One batched call and k single-seed calls must agree to the
        bit: each output row is an independent dot-product row, so the
        seed-axis batching may not perturb any accumulation."""
        rng = np.random.default_rng(m * 31 + n * 7 + k)
        W = 100.0 + rng.random((m, n))
        sq = np.einsum("ij,ij->i", W, W)
        be = get_distance_backend(name)
        h = be.prepare(W, sq)
        idx = rng.choice(m, size=min(k, m), replace=False).tolist()
        batched = be.seed_rows(h, idx)
        per = np.vstack([be.seed_rows(h, [p]) for p in idx])
        np.testing.assert_array_equal(batched, per)

    @pytest.mark.parametrize("m,n,k", [(40, 6, 5), (200, 33, 17),
                                       (97, 5, 24)])
    @pytest.mark.parametrize("name", ["jax", "pallas"])
    def test_matches_float64_brute_force(self, name, m, n, k):
        rng = np.random.default_rng(m + n + k)
        W = 100.0 + rng.random((m, n))
        W[: m // 3] *= 5.0
        sq = np.einsum("ij,ij->i", W, W)
        be = get_distance_backend(name)
        idx = rng.choice(m, size=min(k, m), replace=False).tolist()
        got = be.seed_rows(be.prepare(W, sq), idx)
        want = _brute_rows(W, idx)
        assert got.dtype == np.float64 and got.shape == want.shape
        # f32 Gram-identity cancellation error: ~eps_f32 · |a|²
        np.testing.assert_allclose(got, want, rtol=1e-4,
                                   atol=4e-6 * float(sq.max()))

    def test_multi_k_tile_grid(self):
        """Force the seed axis across multiple kernel tiles
        (block_k < k): tiling the seed axis must not change any row."""
        from repro.kernels import distance as D
        rng = np.random.default_rng(5)
        W = (100.0 + rng.random((150, 9))).astype(np.float32)
        sq = np.einsum("ij,ij->i", W, W)
        idx = np.arange(0, 148, 7, dtype=np.int32)       # k = 22
        one = np.asarray(D.multi_seed_rows(W, sq, idx, interpret=True))
        tiled = np.asarray(D.multi_seed_rows(W, sq, idx, block_k=8,
                                             interpret=True))
        np.testing.assert_array_equal(tiled, one)

    def test_single_seed_delegates_identically(self):
        """seed_rows (the narrow API) is the k=1..few case of the batched
        kernel — same values, no separate code path to drift."""
        from repro.kernels import distance as D
        rng = np.random.default_rng(11)
        W = (10.0 + rng.random((70, 4))).astype(np.float32)
        sq = np.einsum("ij,ij->i", W, W)
        idx = np.asarray([3, 42, 69], dtype=np.int32)
        multi = np.asarray(D.multi_seed_rows(W, sq, idx, interpret=True))
        single = np.asarray(D.seed_rows(W, sq, idx, interpret=True))
        np.testing.assert_array_equal(multi, single)


# -- lockstep device rounds -----------------------------------------------


@pytest.mark.parametrize("name", ["jax", "pallas"])
class TestDeviceLockstep:
    @pytest.mark.parametrize("m,n,seed", [(17, 3, 0), (40, 6, 1),
                                          (64, 8, 2), (200, 5, 3),
                                          (33, 2, 4), (129, 16, 5)])
    def test_cluster_batch_partitions_match_numpy(self, name, m, n, seed):
        """Toggle widths 0..n — the shape of Algorithm 2's per-region
        and composite trials, up to a toggle zeroing EVERY column (a
        matrix of exact zeros: one cluster, whatever residue the deltas
        leave)."""
        rng = np.random.default_rng(seed)
        W = 50.0 + rng.random((m, n))
        W[: max(1, m // 4)] *= 6.0
        dev = IncrementalClusterState(W, backend=name)
        ref = IncrementalClusterState(W)
        toggles = [([], 0.0), (list(range(n)), 0.0)] + \
            [([int(c) for c in rng.choice(n, size=rng.integers(1, n),
                                          replace=False)], 0.0)
             for _ in range(7)]
        got = dev.cluster_batch(toggles)
        want = ref.cluster_batch(toggles)
        for g, w in zip(got, want):
            assert g.n_clusters == w.n_clusters
            assert g.same_partition(w)

    def test_cluster_routes_through_device(self, name, monkeypatch):
        """cluster() on a flat state must take the lockstep path (not
        silently fall back to the host loop)."""
        W = _workload()
        st = IncrementalClusterState(W, backend=name)
        dev = st._device_lockstep()
        assert dev is not None
        calls = []
        orig = dev.cluster_batch
        monkeypatch.setattr(dev, "cluster_batch",
                            lambda cols: calls.append(cols) or orig(cols))
        res = st.cluster()
        assert calls == [[[]]]
        assert res.same_partition(IncrementalClusterState(W).cluster())

    def test_pushed_state_falls_back_to_host(self, name):
        """A non-empty stack (nested trial) must use the exact host path
        — and still match numpy."""
        W = _workload(seed=7)
        a = IncrementalClusterState(W, backend=name)
        b = IncrementalClusterState(W)
        a.push([2], 0.0)
        b.push([2], 0.0)
        assert a.cluster().same_partition(b.cluster())
        (ra,), (rb,) = a.cluster_batch([([1], 0.0)]), \
            b.cluster_batch([([1], 0.0)])
        assert ra.same_partition(rb)

    def test_nonzero_toggle_falls_back_to_host(self, name):
        W = _workload(seed=8)
        a = IncrementalClusterState(W, backend=name)
        b = IncrementalClusterState(W)
        toggles = [([0], 1.5), ([1], 0.0)]
        for ra, rb in zip(a.cluster_batch(toggles),
                          b.cluster_batch(toggles)):
            assert ra.same_partition(rb)

    def test_each_unique_seed_fetched_once_per_state(self, name):
        """The device row cache memo: repeated cluster_batch calls on the
        same state re-fetch nothing, and within one call every unique
        seed costs exactly one cached row."""
        W = _workload(m=60, n=5, seed=9)
        st = IncrementalClusterState(W, backend=name)
        toggles = [([c], 0.0) for c in range(5)] * 3   # duplicate trials
        st.cluster_batch(toggles)
        stats = st.fetch_stats
        assert stats["rows"] == len(stats["per_seed"])
        assert set(stats["per_seed"].values()) == {1}
        rows_before = stats["rows"]
        st.cluster_batch(toggles)       # same seeds -> fully cached
        assert stats["rows"] == rows_before

    def test_batched_fetch_is_one_call_per_round(self, name):
        """All unique seeds a round introduces arrive in ONE backend
        call (the batched multi-seed kernel), not one call per seed."""
        W = _workload(m=80, n=6, seed=10)
        st = IncrementalClusterState(W, backend=name)
        st.cluster_batch([([c], 0.0) for c in range(6)])
        stats = st.fetch_stats
        # every call must have amortized >= 1 seed; if per-seed calls
        # leaked back in, calls would equal rows instead
        assert stats["calls"] <= len(stats["per_seed"])


@pytest.mark.parametrize("name", ["numpy", "jax", "pallas"])
def test_all_zero_trial_is_one_exact_cluster(name):
    """Zeroing every nonzero column leaves a zero matrix — every point at
    distance 0 from every other, so one cluster, on every lane, batched,
    sequential or nested.  (Base rows minus toggle deltas would leave
    roundoff residue there, compared with a zero threshold.)  This is the
    shape of a serving trace's decode-only window, whose prefill column
    is exactly zero."""
    from repro.core import RegionTree, find_dissimilarity_bottlenecks
    rng = np.random.default_rng(3)
    W = np.zeros((6, 4))
    W[:, 1] = 1e-3 * (1.0 + rng.random(6))
    W[:, 3] = 5e-4 * (1.0 + rng.random(6))
    W[:3, 1] *= 3.0
    st = IncrementalClusterState(W, backend=name)
    assert st.cluster().n_clusters > 1
    (zero,) = st.cluster_batch([([1, 3], 0.0)])
    assert zero.n_clusters == 1
    st.push([1, 3], 0.0)
    assert st.cluster().n_clusters == 1
    st.pop()
    st.push([1], 0.0)
    (nested,) = st.cluster_batch([([3], 0.0)])
    assert nested.n_clusters == 1
    st.pop()

    tree = RegionTree("serve")
    for j in range(4):
        tree.add(f"r{j}")
    rids = list(range(1, 5))
    got = find_dissimilarity_bottlenecks(tree, W, rids, backend=name)
    want = find_dissimilarity_bottlenecks(tree, W, rids)
    assert (got.ccrs, got.cccrs, got.composite_s) == \
        (want.ccrs, want.cccrs, want.composite_s)


def _decode_only_lanes(rng):
    """Eight serving lanes over four regions: lanes 0-4 ran only the two
    decode-side regions (1 and 3), lanes 5-7 ran all four, at widely
    separated rates."""
    W = np.zeros((8, 4))
    W[:, 1] = 1e-3 * (1.0 + rng.random(8))
    W[:, 3] = 5e-4 * (1.0 + rng.random(8))
    W[5:, 0] = [0.2, 0.9, 4.0]
    W[5:, 2] = [0.1, 0.5, 2.0]
    return W


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize("name", ["numpy", "jax", "pallas"])
def test_rows_zeroed_by_a_toggle_cluster_exactly(name, seed):
    """Zeroing the decode-side columns leaves lanes 0-4 exactly zero and
    lanes 5-7 not.  The zero rows are one cluster, exactly as clustering
    the zeroed matrix from scratch says, on every lane — batched,
    sequential and nested — though the deltas from the base rows leave
    roundoff residue, compared with a zero threshold, where those rows
    should cancel."""
    from repro.core import optics_cluster
    W = _decode_only_lanes(np.random.default_rng(seed))
    Z = W.copy()
    Z[:, [1, 3]] = 0.0
    want = optics_cluster(Z)
    assert want.n_clusters == 4
    assert len(set(want.labels[:5].tolist())) == 1

    st = IncrementalClusterState(W, backend=name)
    got = st.cluster_batch([([1, 3], 0.0), ([3], 0.0)])
    assert got[0].n_clusters == want.n_clusters
    assert got[0].same_partition(want)
    st.push([1, 3], 0.0)
    seq = st.cluster()
    st.pop()
    st.push([1], 0.0)
    (nested,) = st.cluster_batch([([3], 0.0)])
    st.pop()
    for res in (seq, nested):
        assert res.n_clusters == want.n_clusters
        assert res.same_partition(want)


@pytest.mark.parametrize("name", ["jax", "pallas"])
def test_device_rows_hold_float64_to_threshold_scale(name):
    """The device lanes' D² rows against float64 at the corpus's
    magnitudes (W ~ 100, n = 128), where the Gram identity cancels
    hardest.  The error must stay far below the clustering's threshold²
    (0.01·|a|² at the default 10%).  With its inputs rounded to bf16 on
    the way to a TPU's matrix unit the same Gram misses by about 1e-3·|a|²,
    a tenth of the threshold² and ten times this bound."""
    rng = np.random.default_rng(0)
    W = 100.0 + rng.random((512, 128))
    W[:128] *= 1.05
    sq = np.einsum("ij,ij->i", W, W)
    be = get_distance_backend(name)
    idx = list(range(0, 512, 37))
    got = be.seed_rows(be.prepare(W, sq), idx)
    want = _brute_rows(W, idx)
    err = float(np.abs(got - want).max() / sq[idx].max())
    assert err < 1e-4, f"max |D² error| = {err!r} · |a|²"


class TestHostBatchedFetchMemo:
    def test_unique_seed_fetched_once_per_round(self):
        """Satellite: the host lockstep path stacks each round's unique
        seeds into one backend call, hoisted above the chunk loop —
        trials sharing a seed never duplicate the fetch."""
        W = _workload(m=50, n=4, seed=12)
        st = IncrementalClusterState(W)     # numpy: host path
        # many trials, few distinct seeds per round
        st.cluster_batch([([c % 4], 0.0) for c in range(24)])
        stats = st.fetch_stats
        assert set(stats["per_seed"].values()) == {1}
        assert stats["calls"] <= len(stats["per_seed"])


# -- jitted k-means --------------------------------------------------------


class TestKmeansJax:
    @pytest.mark.parametrize("seed", range(6))
    @pytest.mark.parametrize("k", [2, 3, 5])
    def test_matches_numpy_reference(self, seed, k):
        rng = np.random.default_rng(seed)
        vals = np.concatenate([rng.normal(loc, 0.05, size=rng.integers(3, 9))
                               for loc in (1.0, 5.0, 20.0, 80.0)])
        np.testing.assert_array_equal(kmeans_1d(vals, k, backend="jax"),
                                      kmeans_1d(vals, k))

    def test_degenerate_inputs(self):
        for vals in (np.array([3.0]), np.array([2.0, 2.0, 2.0]),
                     np.array([1.0, 9.0]), np.zeros(0)):
            np.testing.assert_array_equal(
                kmeans_1d(vals, 3, backend="jax"), kmeans_1d(vals, 3))


# -- corpus-wide verdict equality (slow) ----------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("name", ["jax", "pallas"])
def test_synthetic_corpus_verdicts_identical(name):
    """Every synthetic corpus entry's full verdict doc — partitions,
    CCR/CCCR paths, causes, severities — must be identical under the
    accelerated backends.  (CI additionally gates this against the
    committed VERDICTS_synthetic.json on the jax lane.)"""
    from repro.scenarios import corpus_entries
    for entry in corpus_entries(backend="synthetic"):
        tree, collector = entry.build(0)
        rm = collector.collect()
        ref = AutoAnalyzer(tree, **dict(entry.analyzer_kw)).analyze(rm)
        acc = AutoAnalyzer(tree, distance_backend=name,
                           **dict(entry.analyzer_kw)).analyze(rm)
        assert acc.verdict.doc() == ref.verdict.doc(), entry.name
        assert acc.dissimilarity.severity == ref.dissimilarity.severity, \
            entry.name
        assert acc.disparity.severities == ref.disparity.severities, \
            entry.name
