"""Rough-set root-cause analysis vs the paper's worked examples (§4.4)."""
import pytest

from repro.core import (DecisionTable, format_matrix, paper_table2,
                        paper_table3, paper_table4)


class TestPaperTables:
    def test_table2_reducts(self):
        """Paper Eq. 5: cores are {a1,a2} or {a1,a3}."""
        t = paper_table2()
        assert set(t.reducts()) == {frozenset({"a1", "a2"}),
                                    frozenset({"a1", "a3"})}
        assert t.core() == frozenset({"a1"})

    def test_table2_clauses(self):
        t = paper_table2()
        clauses = set(t.discernibility_clauses())
        # after absorption: (a1) ∧ (a2 ∨ a3)
        assert clauses == {frozenset({"a1"}), frozenset({"a2", "a3"})}

    def test_table3_core_is_a5(self):
        """ST dissimilarity: instructions retired (a5) is the root cause."""
        t = paper_table3()
        assert t.reducts() == [frozenset({"a5"})]

    def test_table4_core_is_a2_a3(self):
        """ST disparity: L2 miss rate + disk I/O are the root causes."""
        t = paper_table4()
        assert t.reducts() == [frozenset({"a2", "a3"})]

    def test_table4_per_region_explanations(self):
        t = paper_table4()
        red = t.reducts()[0]
        # region 8 (index 7): root cause = disk I/O (a3)
        assert t.explain(7, red) == ["a3"]
        # region 11 (index 10): root cause = L2 cache miss rate (a2)
        assert t.explain(10, red) == ["a2"]
        # region 14 (index 13): same as 11
        assert t.explain(13, red) == ["a2"]


class TestIndiscernibilityClasses:
    def test_table3_tiled_to_2048_ranks(self, tmp_path):
        """Table 3's 8 processes tiled 256x: the reduct is still {a5}, and
        the clause search compares its 7 classes, not the 2048 ranks."""
        import jax

        from repro.core import spans
        base = paper_table3()
        t = DecisionTable(attributes=base.attributes, rows=base.rows * 256,
                          decisions=base.decisions * 256)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        spans.take()
        with jax.profiler.trace(str(tmp_path), profiler_options=opts):
            reds = t.reducts()
        got = [s.attrs for s in spans.take()["spans"]
               if s.name == "roughset.discernibility"]
        assert reds == [frozenset({"a5"})]
        # decisions 0..4 hold 256, 512, 256, 512, 512 ranks:
        # C(2048, 2) - 2 C(256, 2) - 3 C(512, 2) pairs differ
        assert got == [{"objects": 2048, "pairs": 1638400, "clauses": 1,
                        "classes": 7, "class_pairs": 19}]
        for i in range(len(base.rows)):
            assert t.object_reducts(i) == base.object_reducts(i)
            assert t.object_reducts(i + 8 * 255) == base.object_reducts(i)


class TestMechanics:
    def test_matrix_symmetric_entries(self):
        t = paper_table2()
        m = t.discernibility_matrix()
        n = len(t.rows)
        for i in range(n):
            assert m[i][i] == frozenset()
            for j in range(n):
                assert m[i][j] == m[j][i]

    def test_same_decision_empty_entry(self):
        t = DecisionTable(attributes=["a"], rows=[(1,), (2,)],
                          decisions=[0, 0])
        assert t.discernibility_clauses() == []
        assert t.reducts() == []

    def test_inconsistent_rows_skipped(self):
        # identical attrs, different decision (paper table 4 rows 5/11)
        t = DecisionTable(attributes=["a", "b"],
                          rows=[(1, 0), (1, 0), (0, 0)],
                          decisions=[0, 1, 1])
        reds = t.reducts()
        assert reds == [frozenset({"a"})]

    def test_format_matrix_runs(self):
        s = format_matrix(paper_table2())
        assert "a1" in s and "φ" in s

    def test_arity_validation(self):
        with pytest.raises(ValueError):
            DecisionTable(attributes=["a"], rows=[(1, 2)], decisions=[0])
        with pytest.raises(ValueError):
            DecisionTable(attributes=["a"], rows=[(1,)], decisions=[])


class TestExhaustiveSearchBounds:
    def test_attribute_guard_raises_above_bound(self):
        """The 2^|A| reduct search refuses to start past the attribute
        bound — a modelling error, not a bigger search."""
        n = 22
        base = tuple(0 for _ in range(n))
        rows, decisions = [base], [0]
        for i in range(n):
            r = list(base)
            r[i] = 1
            rows.append(tuple(r))
            decisions.append(1)
        t = DecisionTable(attributes=[f"a{i}" for i in range(n)],
                          rows=rows, decisions=decisions)
        with pytest.raises(ValueError, match="exceeds the exhaustive"):
            t.reducts()
        with pytest.raises(ValueError, match="exceeds the exhaustive"):
            t.object_reducts(0)

    def test_guard_counts_clause_attributes_not_table_columns(self):
        """A wide table whose clauses only involve a few attributes still
        reduces fine."""
        n = 30
        rows = [tuple(0 for _ in range(n)), tuple([1] + [0] * (n - 1))]
        t = DecisionTable(attributes=[f"a{i}" for i in range(n)],
                          rows=rows, decisions=[0, 1])
        assert t.reducts() == [frozenset({"a0"})]

    def test_forced_singleton_pruning_preserves_results(self):
        """Singleton clauses force their attribute into every reduct; the
        pruned search must return exactly the classical answer."""
        t = DecisionTable(
            attributes=["a", "b", "c"],
            rows=[(0, 0, 0), (1, 0, 0), (1, 1, 0), (0, 0, 1)],
            decisions=[0, 1, 2, 3])
        for red in t.reducts():
            assert all(red & c for c in t.discernibility_clauses())
