"""Plain reference of one window's verdict (AutoAnalyzer, arXiv:1103.6087
section 4), written from the paper and the program's documented rules,
importing nothing of the program.

It reads a spool's segments itself (``.npz`` files with a JSON header and
one ``metric:<name>`` array each), reduces the window, and runs the
analysis from scratch on whole matrices: every clustering of Algorithm 2
is a fresh simplified-OPTICS pass over the toggled matrix, with squared
distances taken as sums of squared differences; k-means is the quantile-
initialised 1-D Lloyd loop; rough-set reducts come from the full
discernibility matrix.  ``dtype`` sets the precision of every array, so the
control (float32) is this code with one argument changed.
"""
from __future__ import annotations

import itertools
import json
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

WALL, CPU, FLOPS, BYTES = "wall_time", "cpu_time", "flops", "bytes"
VMEM, HBM, COMM_B, HOST = ("vmem_pressure", "hbm_intensity", "comm_bytes",
                           "host_bytes")
RATES = (VMEM, HBM)
ATTRIBUTES = (VMEM, HBM, HOST, COMM_B, FLOPS)     # the paper's a1..a5
THRESHOLD_FRAC = 0.10       # OPTICS radius: 10% of the seed's norm
FLOOR_DECADES = 0.65        # least span of the severity axis
K = 5                       # severity bands very low .. very high
MEDIUM, HIGH = 2, 3


# -- the spool ---------------------------------------------------------------
def read_window(spool_dir: str, start: int, stop: int):
    """(header of the first covering segment, {metric: (S, R, m, n)})."""
    with open(os.path.join(spool_dir, "spool.json")) as f:
        manifest = json.load(f)
    parts: Dict[str, List[np.ndarray]] = {}
    header = None
    base = None
    for seg in manifest["segments"]:
        s0, s1 = seg["start"], seg["start"] + seg["n_steps"]
        if s0 >= stop or s1 <= start:
            continue
        with np.load(os.path.join(spool_dir, seg["file"])) as z:
            h = json.loads(str(z["__header__"]))
            header = header or h
            base = s0 if base is None else base
            for name in h["metrics"]:
                parts.setdefault(name, []).append(z["metric:" + name])
    data = {k: np.concatenate(v, axis=0)[start - base:stop - base]
            for k, v in parts.items()}
    return header, data


class Tree:
    """Region tree from a trace header's schema (pre-order, root first)."""

    def __init__(self, schema: Sequence[Dict[str, Any]]):
        self.order = [e["id"] for e in schema]
        self.parent = {e["id"]: e["parent"] for e in schema}
        self.name = {e["id"]: e["name"] for e in schema}
        self.management = {e["id"]: bool(e.get("management")) for e in schema}
        self.children: Dict[int, List[int]] = {i: [] for i in self.order}
        for e in schema[1:]:
            self.children[e["parent"]].append(e["id"])

    def depth(self, rid: int) -> int:
        d = 0
        while self.parent[rid] is not None:
            rid, d = self.parent[rid], d + 1
        return d

    def path(self, rid: int) -> str:
        parts = []
        while rid is not None:
            parts.append(self.name[rid])
            rid = self.parent[rid]
        return "/".join(reversed(parts))


# -- reduction ---------------------------------------------------------------
def reduce(data: Dict[str, np.ndarray], meta: Dict[str, Any],
           dtype=np.float64) -> Dict[str, np.ndarray]:
    """Min over repeats; CPU time snapped to wall time where the header's
    CPU-clock tick cannot resolve it (regions without collective bytes);
    rates averaged and quantities summed over steps; bytes per operation
    derived where the collector says so."""
    red = {k: v.astype(dtype).min(axis=1) for k, v in data.items()}
    tick = meta.get("cpu_tick")
    if tick is not None and CPU in red and WALL in red:
        wall, cpu = red[WALL], red[CPU]
        comm = red.get(COMM_B, np.zeros_like(wall))
        snap = (comm == 0) & ((wall < tick) | (np.abs(cpu - wall) < tick))
        red[CPU] = np.where(snap, wall, cpu)
    out = {k: (v.mean(axis=0) if k in RATES else v.sum(axis=0))
           for k, v in red.items()}
    if meta.get("derived") and BYTES in out and FLOPS in out:
        out[HBM] = out[BYTES] / np.maximum(out[FLOPS], 1.0)
    return out


# -- simplified OPTICS (Algorithm 1) ------------------------------------------
def cluster(W: np.ndarray) -> Tuple[np.ndarray, int]:
    """Labels in first-occurrence order and the cluster count.  A seed's
    neighbours are the unassigned points within 10% of its norm."""
    m = W.shape[0]
    labels = np.full(m, -1, dtype=np.int64)
    k = 0
    while True:
        free = np.nonzero(labels < 0)[0]
        if free.size == 0:
            return labels, k
        p = free[0]
        thr = W.dtype.type(THRESHOLD_FRAC) * np.sqrt(np.dot(W[p], W[p]))
        d2 = ((W[free] - W[p]) ** 2).sum(axis=1)
        labels[free[d2 <= thr * thr]] = k
        labels[p] = k
        k += 1


def dissimilarity_severity(labels, k: int, W: np.ndarray) -> float:
    m = W.shape[0]
    if k <= 1 or m <= 1:
        return 0.0
    frac = 1.0 - np.bincount(labels).max() / m
    cents = np.stack([W[labels == c].mean(axis=0) for c in range(k)])
    mean = W.mean(axis=0)
    scale = float(np.linalg.norm(mean)) or 1.0
    spread = float(np.std(np.linalg.norm(cents - mean, axis=1)))
    return min(1.0, frac + spread / (scale + 1e-30))


def algorithm2(tree: Tree, T: np.ndarray, rids: List[int]):
    """(exists, ccrs, cccrs, severity): zero every region deeper than 1;
    a depth-1 region whose zeroing changes the clustering is a CCR, and so
    is a child whose restoring alone reproduces it; windows of adjacent
    depth-1 regions are tried when no single one changes it."""
    col = {rid: j for j, rid in enumerate(rids)}
    memo: Dict[frozenset, Tuple[np.ndarray, int]] = {}

    def clustering(zeroed: frozenset):
        if zeroed not in memo:
            W = T.copy()
            W[:, sorted(zeroed)] = 0
            memo[zeroed] = cluster(W)
        return memo[zeroed]

    def same(a, b) -> bool:
        return a[1] == b[1] and np.array_equal(a[0], b[0])

    walk = [r for r in tree.order if r in col]
    zeroed0 = frozenset(col[r] for r in walk if tree.depth(r) > 1)
    base = clustering(zeroed0)
    if base[1] == 1:
        return False, [], [], 0.0
    W0 = T.copy()
    W0[:, sorted(zeroed0)] = 0
    severity = dissimilarity_severity(base[0], base[1], W0)
    ccrs: List[int] = []
    cccrs: List[int] = []

    def children(parent: int, zeroed: frozenset) -> bool:
        found = False
        for c in tree.children[parent]:
            if c not in col:
                continue
            z = zeroed - {col[c]}
            if same(clustering(z), base):
                ccrs.append(c)
                found = True
                deeper = children(c, z)
                if not tree.children[c] or not deeper:
                    cccrs.append(c)
        return found

    d1 = [r for r in walk if tree.depth(r) == 1]
    for r in d1:
        z = zeroed0 | {col[r]}
        if not same(clustering(z), base):
            ccrs.append(r)
            had = children(r, z)
            if not tree.children[r] or not had:
                cccrs.append(r)
    s = 2
    while not ccrs and s <= max(len(d1) - 1, 2) and s <= len(d1):
        for a in range(len(d1) - s + 1):
            w = d1[a:a + s]
            if not same(clustering(zeroed0 | {col[g] for g in w}), base):
                ccrs.extend(w)
                cccrs.extend(w)
        s += 1
    return True, sorted(set(ccrs)), sorted(set(cccrs)), severity


# -- k-means severity (disparity) ---------------------------------------------
def kmeans_1d(x: np.ndarray, k: int, n_iter: int = 100) -> np.ndarray:
    """Lloyd's iterations from the k quantiles; labels ranked by centroid."""
    uniq = np.unique(x)
    if uniq.size <= k:
        return np.searchsorted(uniq, x).astype(np.int64)
    cents = np.quantile(x, np.linspace(0, 1, k))
    lab = np.zeros(x.size, dtype=np.int64)
    for _ in range(n_iter):
        lab = np.argmin(np.abs(x[:, None] - cents[None, :]), axis=1)
        counts = np.bincount(lab, minlength=k)
        sums = np.bincount(lab, weights=x, minlength=k)
        new = np.where(counts > 0, sums / np.maximum(counts, 1), cents)
        if np.allclose(new, cents):
            break
        cents = new
    rank = np.empty(k, dtype=np.int64)
    rank[np.argsort(cents)] = np.arange(k)
    return rank[lab]


def _log_axis(values: np.ndarray) -> np.ndarray:
    return np.log10(np.maximum(values, values.max() * 1e-4))


def severity_bands(values: np.ndarray) -> np.ndarray:
    """Band per value: k-means in log space, centroids closer than 3% of
    the range merged, each band placed by its centroid on an axis at least
    FLOOR_DECADES long."""
    if values.size == 0 or values.max() <= 0:
        return np.zeros(values.size, dtype=np.int64)
    x = _log_axis(values)
    labels = kmeans_1d(x, min(K, x.size))
    cents = np.array([x[labels == c].mean() if (labels == c).any()
                      else -np.inf for c in range(labels.max() + 1)])
    rng = x.max() - x.min()
    groups: List[List[int]] = []
    for c in np.argsort(cents):
        if not np.isfinite(cents[c]):
            continue
        if groups and rng > 0 and \
                cents[c] - cents[groups[-1][-1]] < 0.03 * rng:
            groups[-1].append(c)
        else:
            groups.append([c])
    rng = max(rng, FLOOR_DECADES)
    band = {}
    for g in groups:
        frac = (np.mean([cents[c] for c in g]) - x.min()) / rng
        for c in g:
            band[c] = int(np.round((K - 1) * frac))
    return np.array([band[c] for c in labels], dtype=np.int64)


def disparity(tree: Tree, values: np.ndarray, wall: np.ndarray,
              rids: List[int]) -> Tuple[List[int], List[int]]:
    """(ccrs, cccrs).  A region with measured children is banded on the
    share of its time it spends itself, then takes at least its hottest
    child's band; bands from high up are CCRs, and a CCR is a CCCR when
    it is a leaf, has no CCR child, or is banded above all of them."""
    sev = severity_bands(values)
    idx = {rid: j for j, rid in enumerate(rids)}
    excl = wall.copy()
    for rid, j in idx.items():
        excl[j] = max(wall[j] - sum(wall[idx[c]] for c in tree.children[rid]
                                    if c in idx), 0.0)
    ratios = np.where(wall > 0, excl / np.maximum(wall, 1e-30), 1.0)
    top = values.max()
    if (ratios < 1).any() and top > 0:
        x = _log_axis(values)
        lo, rng = x.min(), max(x.max() - x.min(), FLOOR_DECADES)
        for j in np.nonzero(ratios < 1.0)[0]:
            u = np.log10(max(values[j] * ratios[j], top * 1e-4))
            sev[j] = min(sev[j], int(np.clip(np.round((K - 1) * (u - lo)
                                                      / rng), 0, K - 1)))
    for rid in sorted(rids, key=tree.depth, reverse=True):
        p = tree.parent[rid]
        if p in idx:
            sev[idx[p]] = max(sev[idx[p]], sev[idx[rid]])
    band = {rid: int(sev[j]) for rid, j in idx.items()}
    ccrs = [rid for rid in rids if band[rid] >= HIGH]
    cccrs = []
    for rid in ccrs:
        kids = [c for c in tree.children[rid] if c in ccrs]
        if not kids or all(band[rid] > band[c] for c in kids):
            cccrs.append(rid)
    return sorted(ccrs), sorted(cccrs)


# -- rough sets ---------------------------------------------------------------
def _min_hitting_sets(clauses: Sequence[int], n_attr: int) -> List[int]:
    """All minimum-size attribute sets (bit masks) meeting every clause."""
    clauses = set(c for c in clauses if c)
    clauses = [c for c in clauses
               if not any(o != c and o & c == o for o in clauses)]
    if not clauses:
        return []
    for size in range(1, n_attr + 1):
        hits = [sum(1 << a for a in combo)
                for combo in itertools.combinations(range(n_attr), size)]
        hits = [h for h in hits if all(h & c for c in clauses)]
        if hits:
            return hits
    return []


def _diff_masks(rows: np.ndarray, dec: np.ndarray, i: Optional[int] = None):
    """Bit masks of the attributes on which objects of different decision
    differ: every such pair, or object ``i`` against the others."""
    weights = 1 << np.arange(rows.shape[1])
    if i is None:
        masks = ((rows[:, None, :] != rows[None, :, :]) * weights).sum(-1)
        return np.unique(masks[dec[:, None] != dec[None, :]])
    masks = ((rows[i][None, :] != rows) * weights).sum(-1)
    return np.unique(masks[dec != dec[i]])


def _names(mask: int) -> List[str]:
    return [a for b, a in enumerate(ATTRIBUTES) if mask >> b & 1]


# -- the verdict ----------------------------------------------------------------
def analyze(tree: Tree, rids: List[int], rm: Dict[str, np.ndarray],
            dtype=np.float64) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """(verdict document, numbers): the document in the program's
    canonical form; numbers holds the per-region disparity values and the
    dissimilarity severity."""
    all_cols = list(rids)
    rids = [r for r in rids if not tree.management.get(r, False)]
    cols = [all_cols.index(r) for r in rids]
    m = next(iter(rm.values())).shape[0]

    def vec(name: str) -> np.ndarray:
        return (rm[name] if name in rm else np.zeros((m, len(all_cols)),
                                                     dtype))[:, cols]

    exists, ccrs, cccrs, severity = algorithm2(tree, vec(CPU), rids)
    # Disparity: CRNM = region wall / program wall * CPU time per operation,
    # averaged over processes and scaled to its maximum.
    wall_all = rm[WALL]
    wp = wall_all.sum(axis=1)
    wp = np.where(wp <= 0, 1e-30, wp)
    W, F, C = vec(WALL), vec(FLOPS), vec(CPU)
    cpi = np.where(F > 0, C / np.maximum(F, 1.0), 0.0)
    crnm = (W / wp[:, None] * cpi).mean(axis=0)
    if crnm.max() > 0:
        crnm = crnm / crnm.max()
    d_ccrs, d_cccrs = disparity(tree, crnm, W.mean(axis=0), rids)

    dis_attrs: set = set()
    if exists:
        dec, _ = cluster(vec(CPU))
        rows = np.stack([cluster(vec(a))[0] for a in ATTRIBUTES], axis=1)
        for h in _min_hitting_sets(_diff_masks(rows, dec), len(ATTRIBUTES)):
            dis_attrs |= set(_names(h))
    bits = np.stack([severity_bands(vec(a).mean(axis=0)) > MEDIUM
                     for a in ATTRIBUTES], axis=1).astype(np.int64)
    dec = np.array([1 if r in d_ccrs else 0 for r in rids])
    per_path = []
    disp_attrs: set = set()
    for rid in d_ccrs:
        i = rids.index(rid)
        pos = set()
        for h in _min_hitting_sets(_diff_masks(bits, dec, i),
                                   len(ATTRIBUTES)):
            pos |= {a for a in _names(h) if bits[i, ATTRIBUTES.index(a)]}
        disp_attrs |= pos
        per_path.append((tree.path(rid), sorted(pos)))
    doc = {
        "dissimilar": bool(exists),
        "dissimilarity_paths": sorted(tree.path(r) for r in cccrs),
        "dissimilarity_ccr_paths": sorted(tree.path(r) for r in ccrs),
        "disparity_paths": sorted(tree.path(r) for r in d_cccrs),
        "disparity_ccr_paths": sorted(tree.path(r) for r in d_ccrs),
        "cause_attributes": sorted(dis_attrs | disp_attrs),
        "dissimilarity_cause_attributes": sorted(dis_attrs),
        "per_path_causes": [[p, a] for p, a in sorted(per_path)],
    }
    numbers = {"values": {rid: float(v) for rid, v in zip(rids, crnm)},
               "severity": float(severity)}
    return doc, numbers


def analyze_window(spool_dir: str, start: int, stop: int, dtype=np.float64):
    header, data = read_window(spool_dir, start, stop)
    rm = reduce(data, header.get("meta", {}), dtype)
    return analyze(Tree(header["schema"]), list(header["region_ids"]), rm,
                   dtype)


def value_gap(program: Dict[str, Any], reference: Dict[str, Any]) -> float:
    """Widest gap of the window's numbers: per-region disparity values
    relative to the largest, and the dissimilarity severity (in [0, 1])."""
    ref = reference["values"]
    scale = max(abs(v) for v in ref.values()) or 1.0
    gap = max(abs(program["values"][r] - v) / scale for r, v in ref.items())
    return float(max(gap, abs(program["severity"] - reference["severity"])))
