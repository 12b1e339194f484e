"""Property-based tests (hypothesis) for the system's invariants."""
import itertools
from unittest import mock

import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="hypothesis not installed")
from hypothesis import given, settings, strategies as st

from repro.core import (DecisionTable, RegionMetrics, kmeans_severity,
                        optics_cluster, roughset)
from repro.optim import dequantize_int8, quantize_int8

nice_floats = st.floats(min_value=0.0, max_value=1e6, allow_nan=False,
                        allow_infinity=False, width=32)


@st.composite
def matrices(draw, max_m=12, max_n=8):
    m = draw(st.integers(2, max_m))
    n = draw(st.integers(1, max_n))
    rows = draw(st.lists(st.lists(nice_floats, min_size=n, max_size=n),
                         min_size=m, max_size=m))
    return np.array(rows, dtype=np.float64)


class TestOpticsProperties:
    @given(matrices())
    @settings(max_examples=60, deadline=None)
    def test_every_point_labelled(self, v):
        res = optics_cluster(v)
        assert res.labels.min() >= 0
        assert res.labels.max() == res.n_clusters - 1
        assert set(res.labels) == set(range(res.n_clusters))

    @given(matrices())
    @settings(max_examples=60, deadline=None)
    def test_duplicated_point_same_cluster(self, v):
        """A point identical to another always shares its cluster."""
        v2 = np.vstack([v, v[0:1]])
        res = optics_cluster(v2)
        assert res.labels[0] == res.labels[-1]

    @given(st.integers(2, 16), st.integers(1, 8))
    @settings(max_examples=30, deadline=None)
    def test_identical_rows_single_cluster(self, m, n):
        v = np.full((m, n), 3.14)
        assert optics_cluster(v).n_clusters == 1

    @given(matrices(), st.floats(0.1, 100.0))
    @settings(max_examples=40, deadline=None)
    def test_scale_invariance(self, v, s):
        """The paper's threshold is relative (10% of ||V||), so uniform
        scaling preserves the partition."""
        a = optics_cluster(v)
        b = optics_cluster(v * s)
        assert a.n_clusters == b.n_clusters


class TestKMeansSeverityProperties:
    @given(st.lists(nice_floats, min_size=1, max_size=40))
    @settings(max_examples=60, deadline=None)
    def test_labels_in_range(self, vals):
        sev = kmeans_severity(np.array(vals))
        assert ((0 <= sev) & (sev <= 4)).all()

    @given(st.lists(st.floats(0.0009765625, 1e6, allow_nan=False, width=32),
                    min_size=2, max_size=30))
    @settings(max_examples=60, deadline=None)
    def test_monotone_in_value(self, vals):
        """A larger value never gets a lower severity."""
        x = np.array(vals)
        sev = kmeans_severity(x)
        order = np.argsort(x)
        s_sorted = sev[order]
        assert all(a <= b for a, b in zip(s_sorted, s_sorted[1:]))

    @given(st.lists(st.floats(0.0009765625, 1e6, allow_nan=False, width=32),
                    min_size=2, max_size=30))
    @settings(max_examples=40, deadline=None)
    def test_max_value_gets_top_band_when_spread(self, vals):
        x = np.array(vals)
        if x.max() / max(x.min(), 1e-9) > 100:
            sev = kmeans_severity(x)
            assert sev[int(np.argmax(x))] == 4


@st.composite
def decision_tables(draw):
    n_attr = draw(st.integers(1, 5))
    n_rows = draw(st.integers(2, 10))
    rows = [tuple(draw(st.integers(0, 2)) for _ in range(n_attr))
            for _ in range(n_rows)]
    decisions = [draw(st.integers(0, 2)) for _ in range(n_rows)]
    return DecisionTable(attributes=[f"a{i}" for i in range(n_attr)],
                         rows=rows, decisions=decisions)


class TestRoughSetProperties:
    @given(decision_tables())
    @settings(max_examples=60, deadline=None)
    def test_reducts_hit_every_clause(self, t):
        clauses = t.discernibility_clauses()
        for red in t.reducts():
            assert all(red & c for c in clauses)

    @given(decision_tables())
    @settings(max_examples=60, deadline=None)
    def test_reducts_minimal(self, t):
        clauses = t.discernibility_clauses()
        for red in t.reducts():
            for a in red:
                smaller = red - {a}
                assert not all(smaller & c for c in clauses)

    @given(decision_tables())
    @settings(max_examples=60, deadline=None)
    def test_core_is_intersection(self, t):
        reds = t.reducts()
        if reds:
            inter = frozenset.intersection(*reds)
            assert t.core() == inter

    @given(decision_tables())
    @settings(max_examples=40, deadline=None)
    def test_object_reducts_subset_of_attrs(self, t):
        for i in range(len(t.rows)):
            for red in t.object_reducts(i):
                assert red <= frozenset(t.attributes)


def _pair_loop_clauses(t, objects):
    """The absorption-minimal clauses of every pair (i, j), i in
    ``objects``, whose decisions differ: the brute-force oracle."""
    clauses = set()
    for i in objects:
        for j in range(len(t.rows)):
            if t.decisions[i] != t.decisions[j]:
                diff = frozenset(a for k, a in enumerate(t.attributes)
                                 if t.rows[i][k] != t.rows[j][k])
                if diff:
                    clauses.add(diff)
    return {c for c in clauses if not any(o < c for o in clauses)}


def _brute_reducts(clauses):
    attrs = sorted({a for c in clauses for a in c})
    for size in range(1, len(attrs) + 1):
        hits = {frozenset(s) for s in itertools.combinations(attrs, size)
                if all(set(s) & c for c in clauses)}
        if hits:
            return hits
    return set()


# Values that are equal across types (1 == 1.0 == True) as well as apart.
_VALUES = st.sampled_from([0, 1, 2, 1.0, True, False, "lo", "hi"])


@st.composite
def tiled_tables(draw):
    """2-6 distinct rows tiled to 50-300 objects; a few objects take
    another decision than their row's, so inconsistent pairs occur."""
    n_attr = draw(st.integers(1, 6))
    distinct = draw(st.lists(st.tuples(*[_VALUES] * n_attr),
                             min_size=2, max_size=6))
    row_dec = [draw(st.sampled_from([0, 1, 2, "N"])) for _ in distinct]
    rnd = draw(st.randoms(use_true_random=False))
    rows, decisions = [], []
    for _ in range(draw(st.integers(50, 300))):
        k = rnd.randrange(len(distinct))
        rows.append(distinct[k])
        decisions.append(row_dec[k] if rnd.random() < 0.9
                         else rnd.choice([0, 1, 2, "N"]))
    return DecisionTable(attributes=[f"a{i}" for i in range(n_attr)],
                         rows=rows, decisions=decisions)


@st.composite
def distinct_tables(draw):
    n_attr = draw(st.integers(1, 5))
    rows = draw(st.lists(st.tuples(*[st.integers(0, 9)] * n_attr),
                         min_size=2, max_size=60, unique=True))
    decisions = [draw(st.integers(0, 3)) for _ in rows]
    return DecisionTable(attributes=[f"a{i}" for i in range(n_attr)],
                         rows=rows, decisions=decisions)


class TestClassReductionMatchesPairLoop:
    """The clause search over indiscernibility classes gives the clauses
    of the loop over every pair of objects, whatever the block of class
    pairs and the width of a bitmask word."""

    @pytest.mark.parametrize("tables", [tiled_tables(), distinct_tables(),
                                        decision_tables()],
                             ids=["tiled", "distinct", "small"])
    @given(data=st.data(), block=st.sampled_from([1, 3, 64, 1 << 20]),
           word=st.sampled_from([1, 2, 62]))
    @settings(max_examples=40, deadline=None)
    def test_clauses_reducts_and_object_clauses(self, tables, data, block,
                                                word):
        t = data.draw(tables)
        with mock.patch.object(roughset, "_BLOCK", block), \
                mock.patch.object(roughset, "_WORD", word):
            clauses = t.discernibility_clauses()
            reducts = t.reducts()
            per_object = [t.object_clauses(i) for i in range(len(t.rows))]
        want = _pair_loop_clauses(t, range(len(t.rows)))
        assert clauses == sorted(want, key=lambda c: (len(c), sorted(c)))
        assert set(reducts) == _brute_reducts(want)
        for i, got in enumerate(per_object):
            assert len(got) == len(set(got))
            assert set(got) == _pair_loop_clauses(t, [i])


class TestCRNMProperties:
    @given(st.lists(st.floats(0.015625, 100.0, allow_nan=False, width=32),
                    min_size=3, max_size=10), st.floats(0.5, 20.0))
    @settings(max_examples=40, deadline=None)
    def test_crnm_time_scale_invariant_ranking(self, times, s):
        """Scaling all region times equally preserves the CRNM ranking."""
        n = len(times)
        rids = list(range(1, n + 1))

        def build(scale):
            rm = RegionMetrics(region_ids=rids, n_processes=2)
            for i in range(2):
                for j, rid in enumerate(rids):
                    rm.set("wall_time", i, rid, times[j] * scale)
                    rm.set("cpu_time", i, rid, times[j] * scale)
                    rm.set("flops", i, rid, times[j] * scale * 1e9)
            return rm.crnm_all(rids)

        a, b = build(1.0), build(s)
        # scale-free up to float roundoff: compare normalized values
        np.testing.assert_allclose(a / max(a.max(), 1e-30),
                                   b / max(b.max(), 1e-30), rtol=1e-5)


class TestQuantizationProperties:
    @given(st.lists(st.floats(-1e4, 1e4, allow_nan=False, width=32),
                    min_size=1, max_size=64))
    @settings(max_examples=60, deadline=None)
    def test_int8_roundtrip_error_bound(self, vals):
        import jax.numpy as jnp
        x = jnp.array(vals, jnp.float32)
        q, scale = quantize_int8(x)
        y = dequantize_int8(q, scale)
        amax = float(jnp.max(jnp.abs(x)))
        # error bounded by half a quantization step
        assert float(jnp.max(jnp.abs(x - y))) <= amax / 127.0 * 0.5 + 1e-6
