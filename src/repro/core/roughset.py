"""Rough-set root-cause analysis (paper §4.4).

Implements decision systems, the decision-relative discernibility matrix
(Eq. 3), the discernibility function (Eq. 4), and the extraction of core
attributes / reducts.  The paper's "core attributions" are the minimal
conjunctive attribute sets shared by the discernibility functions — i.e. the
*minimal reducts* (prime implicants of the CNF discernibility function); we
expose both those and the classical core (intersection of all reducts).

Worked examples from the paper are unit-tested:
  * Table 2  -> reducts {a1,a2} and {a1,a3}
  * Table 3  -> unique reduct {a5}     (ST dissimilarity)
  * Table 4  -> unique reduct {a2,a3}  (ST disparity)
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
from typing import Dict, FrozenSet, List, Optional, Sequence, Tuple

import numpy as np

from .spans import span

# The reduct search enumerates attribute subsets by size — O(2^|A|) in the
# worst case.  The paper's decision tables have 5 attributes; anything past
# this bound is a modelling error, not a bigger search.
MAX_EXHAUSTIVE_ATTRIBUTES = 20

# Class pairs per block of the clause search: bounds its working memory
# (8 MB a bitmask word) whatever the number of classes.
_BLOCK = 1 << 20
# Attributes per bitmask word: a word stays a non-negative int64.
_WORD = 62


def _factorise(rows: Sequence[Tuple], width: int) -> np.ndarray:
    """Each column's values as integer codes, equal values (hash-and-``==``)
    sharing a code: a (len(rows), width) int64 array."""
    cols: List[Dict] = [{} for _ in range(width)]
    codes = [[col.setdefault(v, len(col)) for col, v in zip(cols, r)]
             for r in rows]
    return np.array(codes, dtype=np.int64).reshape(len(rows), width)


def _difference_masks(codes: np.ndarray, dec: np.ndarray) -> Tuple[set, int]:
    """The distinct sets of differing attributes over the pairs i < j of
    ``codes`` rows whose ``dec`` differ, each as an int whose bit k is
    attribute k; and the number of pairs compared."""
    n, width = codes.shape
    n_words = max(1, -(-width // _WORD))
    masks: set = set()
    compared = 0
    step = max(1, _BLOCK // max(1, n))
    for lo in range(0, n, step):
        hi, rest = min(n, lo + step), slice(lo + 1, n)
        keep = ((np.arange(lo + 1, n) > np.arange(lo, hi)[:, None])
                & (dec[lo:hi, None] != dec[None, rest]))
        words = np.zeros((n_words,) + keep.shape, np.int64)
        for k in range(width):
            words[k // _WORD] |= ((codes[lo:hi, None, k]
                                   != codes[None, rest, k]) << k % _WORD)
        pairs = words[:, keep]
        compared += pairs.shape[1]
        uniq = (np.unique(pairs[0])[:, None] if n_words == 1
                else np.unique(pairs.T, axis=0))
        masks.update(sum(int(w) << _WORD * i for i, w in enumerate(u))
                     for u in uniq)
    return masks, compared


def _minimal_hitting_sets(
        clauses: Sequence[FrozenSet[str]]) -> List[FrozenSet[str]]:
    """All minimum-size hitting sets of ``clauses`` (the search stops at
    the first productive size: larger hitting sets are either supersets of
    a found one or outside the paper's 'core attributions' notion).

    Pruning that provably cannot change the result: any hitting set must
    contain every attribute that appears as a singleton clause
    (``forced``), so candidates missing one — and sizes below
    ``len(forced)`` — are skipped before the clause scan.
    """
    attrs = sorted({a for c in clauses for a in c})
    if len(attrs) > MAX_EXHAUSTIVE_ATTRIBUTES:
        raise ValueError(
            f"reduct search over {len(attrs)} attributes exceeds the "
            f"exhaustive-search bound ({MAX_EXHAUSTIVE_ATTRIBUTES}); "
            "decision tables are expected to stay near the paper's 5 "
            "attributes — reduce the attribute set or use a heuristic "
            "reducer")
    forced = frozenset(a for c in clauses if len(c) == 1 for a in c)
    hits: List[FrozenSet[str]] = []
    with span("roughset.reducts", clauses=len(clauses)):
        for size in range(max(1, len(forced)), len(attrs) + 1):
            for combo in itertools.combinations(attrs, size):
                s = frozenset(combo)
                if not forced <= s:
                    continue  # misses a singleton clause
                if all(s & c for c in clauses):
                    hits.append(s)
            if hits:
                break  # all minimum-size hitting sets found
    return hits


@dataclasses.dataclass
class DecisionTable:
    """A decision system Λ = (U, A ∪ {d}).

    ``rows[i]`` holds the conditional attribute values of object i;
    ``decisions[i]`` its decision value.  Values may be any hashable.
    """

    attributes: List[str]
    rows: List[Tuple]
    decisions: List
    object_ids: Optional[List] = None

    def __post_init__(self) -> None:
        if self.object_ids is None:
            self.object_ids = list(range(len(self.rows)))
        for r in self.rows:
            if len(r) != len(self.attributes):
                raise ValueError("row arity mismatch")
        if len(self.decisions) != len(self.rows):
            raise ValueError("decision arity mismatch")

    # -- Eq. 3 ----------------------------------------------------------
    def discernibility_matrix(self) -> List[List[FrozenSet[str]]]:
        """c_ij = {a in A : a(x_i) != a(x_j)}  if d(x_i) != d(x_j) else ∅."""
        n = len(self.rows)
        mat = [[frozenset() for _ in range(n)] for _ in range(n)]
        for i in range(n):
            for j in range(i + 1, n):
                if self.decisions[i] != self.decisions[j]:
                    diff = frozenset(
                        a for k, a in enumerate(self.attributes)
                        if self.rows[i][k] != self.rows[j][k])
                    mat[i][j] = mat[j][i] = diff
        return mat

    # -- Eq. 4 ----------------------------------------------------------
    def _classes(self) -> List[int]:
        """One object of each indiscernibility class of U/IND(A ∪ {d}),
        in first-seen order.

        Two objects with equal rows and equal decisions add the same
        clause, or none, against any third object, so the clauses over
        these representatives are the clauses over all objects.  Equality
        is hash-and-``==``, as the ``!=`` of Eq. 3.
        """
        first: Dict[Tuple, int] = {}
        for i, key in enumerate(zip(self.rows, self.decisions)):
            first.setdefault(key, i)
        return list(first.values())

    def discernibility_clauses(self) -> List[FrozenSet[str]]:
        """The non-empty, absorption-minimal clauses of f_Λ (CNF).

        Empty entries for *differing* decisions (inconsistent objects, which
        do occur — e.g. paper Table 4 rows 5 vs 11) are skipped, the standard
        treatment for inconsistent decision systems.

        Only class representatives are compared, as integer codes in
        blocks of pairs: a table of a few distinct rows tiled over
        thousands of ranks costs what the few rows cost.
        """
        n = len(self.rows)
        with span("roughset.discernibility", objects=n) as sp:
            reps = self._classes()
            codes = _factorise([self.rows[i] for i in reps],
                               len(self.attributes))
            dec = _factorise([(self.decisions[i],) for i in reps], 1)[:, 0]
            masks, compared = _difference_masks(codes, dec)
            clauses = {frozenset(a for k, a in enumerate(self.attributes)
                                 if m >> k & 1)
                       for m in masks if m}
            # Absorption: drop any clause that is a superset of another.
            minimal = [c for c in clauses
                       if not any(o < c for o in clauses)]
            if sp:
                same = collections.Counter(self.decisions).values()
                sp.set(pairs=n * (n - 1) // 2
                       - sum(c * (c - 1) // 2 for c in same),
                       clauses=len(minimal), classes=len(reps),
                       class_pairs=compared)
        return sorted(minimal, key=lambda c: (len(c), sorted(c)))

    # -- reducts / core --------------------------------------------------
    def reducts(self) -> List[FrozenSet[str]]:
        """All minimal hitting sets of the discernibility clauses — the
        prime implicants of f_Λ, i.e. the paper's 'core attributions'."""
        clauses = self.discernibility_clauses()
        if not clauses:
            return []
        hits = _minimal_hitting_sets(clauses)
        return sorted(hits, key=lambda s: (len(s), sorted(s)))

    def object_clauses(self, index: int) -> List[FrozenSet[str]]:
        """Clauses of the per-object discernibility function f_i (the paper
        computes 'the discernibility functions of each object'): object i
        against one representative of each class of another decision."""
        n = len(self.rows)
        clauses = set()
        with span("roughset.discernibility", objects=n) as sp:
            reps = self._classes()
            row, decision = self.rows[index], self.decisions[index]
            others = [j for j in reps if self.decisions[j] != decision]
            for j in others:
                diff = frozenset(
                    a for k, a in enumerate(self.attributes)
                    if row[k] != self.rows[j][k])
                if diff:
                    clauses.add(diff)
            minimal = [c for c in clauses
                       if not any(o < c for o in clauses)]
            if sp:
                sp.set(pairs=n - self.decisions.count(decision),
                       clauses=len(minimal), classes=len(reps),
                       class_pairs=len(others))
        return minimal

    def object_reducts(self, index: int) -> List[FrozenSet[str]]:
        """Minimal hitting sets of the per-object clauses: the attributes
        that explain why object i is classified apart (its root causes)."""
        clauses = self.object_clauses(index)
        if not clauses:
            return []
        hits = _minimal_hitting_sets(clauses)
        return sorted(hits, key=lambda s: sorted(s))

    def core(self) -> FrozenSet[str]:
        """Classical core = intersection of all reducts = union of singleton
        clauses."""
        reds = self.reducts()
        if not reds:
            return frozenset()
        out = reds[0]
        for r in reds[1:]:
            out = out & r
        return out

    # -- per-object explanation ------------------------------------------
    def explain(self, index: int,
                reduct: Optional[FrozenSet[str]] = None,
                positive=lambda v: bool(v)) -> List[str]:
        """Paper: 'we search the decision table and find the root cause of
        code region 8 is high disk I/O quantity' — for one object, the
        reduct attributes whose value is 'high' (positive)."""
        if reduct is None:
            reds = self.reducts()
            reduct = reds[0] if reds else frozenset()
        row = self.rows[index]
        return [a for k, a in enumerate(self.attributes)
                if a in reduct and positive(row[k])]


def format_matrix(table: DecisionTable) -> str:
    """Render the discernibility matrix (paper Fig. 3 / Fig. 10)."""
    mat = table.discernibility_matrix()
    n = len(table.rows)
    lines = []
    for i in range(n):
        cells = []
        for j in range(n):
            if j <= i:
                cells.append(".")
            else:
                cells.append(",".join(sorted(mat[i][j])) or "φ")
        lines.append(" | ".join(cells))
    return "\n".join(lines)


def paper_table2() -> DecisionTable:
    """The weather example (paper Table 2)."""
    return DecisionTable(
        attributes=["a1", "a2", "a3", "a4"],
        rows=[("sunny", "hot", "high", False),
              ("sunny", "hot", "high", True),
              ("overcast", "hot", "high", False),
              ("sunny", "cool", "low", False)],
        decisions=["N", "N", "P", "P"],
    )


def paper_table3() -> DecisionTable:
    """ST dissimilarity decision table (paper Table 3)."""
    rows = [(0, 0, 0, 0, 0), (0, 0, 0, 0, 1), (0, 0, 0, 0, 1),
            (1, 0, 0, 0, 2), (0, 1, 0, 0, 3), (1, 1, 0, 1, 4),
            (1, 2, 0, 1, 3), (1, 2, 0, 0, 4)]
    return DecisionTable(
        attributes=["a1", "a2", "a3", "a4", "a5"],
        rows=rows,
        decisions=[0, 1, 1, 2, 3, 4, 3, 4],
    )


def paper_table4() -> DecisionTable:
    """ST disparity decision table (paper Table 4).  Rows 5 and 11 are an
    inconsistent pair (same attributes, different decision)."""
    rows = {
        1: (0, 0, 0, 0, 0), 2: (1, 0, 0, 0, 0), 3: (0, 0, 0, 0, 0),
        4: (0, 0, 0, 0, 0), 5: (1, 1, 0, 0, 1), 6: (1, 0, 0, 0, 1),
        7: (0, 0, 0, 0, 0), 8: (0, 0, 1, 0, 1), 9: (1, 0, 0, 0, 0),
        10: (1, 0, 0, 0, 0), 11: (1, 1, 0, 0, 1), 12: (0, 0, 0, 0, 0),
        13: (0, 0, 0, 0, 0), 14: (1, 1, 0, 0, 1),
    }
    dec = {i: (1 if i in (8, 11, 14) else 0) for i in rows}
    ids = sorted(rows)
    return DecisionTable(
        attributes=["a1", "a2", "a3", "a4", "a5"],
        rows=[rows[i] for i in ids],
        decisions=[dec[i] for i in ids],
        object_ids=ids,
    )
