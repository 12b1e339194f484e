"""Device lockstep greedy rounds for ``IncrementalClusterState``.

The host batched path of :meth:`IncrementalClusterState.cluster_batch`
still does O(trials) Python/numpy work per greedy round (one einsum per
trial for the toggle delta).  At fleet shapes — m = 16384 shards, one
trial per region — that host loop dominates Algorithm 2's cost.  This
module evaluates the same lockstep rounds as a handful of jitted device
dispatches per round instead:

* toggled columns are gathered once per batch (``_prep``) into a
  (trials, w, m) tensor against a sentinel-padded transpose of the
  point matrix (column ``n`` is identically zero, so padded toggle
  slots contribute nothing);
* each round is **one** fused dispatch (``_round``): per-trial seed-row
  deltas, thresholds, neighbourhood candidacy, the count gate and the
  label/cluster-count updates all happen on device, with the mutable
  per-trial state (labels, cluster counts, thresholds) **donated** back
  to the next round so repeated rounds — and repeated per-window
  analyses — reuse buffers instead of reallocating;
* base D² seed rows are fetched through the distance backend's batched
  device call (``device_rows`` — one Pallas/XLA call for *all* unique
  seeds a round introduces) and cached in a device-resident row cache
  that persists across rounds, sibling trial groups and windows of the
  same state, so each unique seed is fetched at most once per state.

Only zero-toggles at stack depth 0 are eligible (exactly the shape of
Algorithm 2's depth-1 sweep, its composite-window rounds, and the
baseline clustering); everything else falls back to the host path.
The exact float64 numpy backend never routes here — bit-for-bit
equality between batched and sequential evaluation stays pinned by
tests/test_trial_batching.py — while the jax/pallas device path is
validated partition-for-partition and verdict-for-verdict against it
(tests/test_device_lockstep.py, the corpus gates).

All jitted entry points live at module level so their compile caches
are shared by every state instance: an OnlineAnalyzer window loop at a
fixed (m, n) pays tracing once, then every subsequent window amortizes
to pure dispatch.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from .spans import span


@functools.partial(jax.jit, static_argnames=("n",))
def _prep(Wd, Ad, nnz, cols, *, n):
    """Per-trial toggled-column gathers.

    Wd/Ad : (m, n) points and their elementwise squares.
    nnz   : (m,) int32 count of nonzero entries per point.
    cols  : (nt, w) int32 toggled-column ids (sentinel ``n`` pads).
    Returns ``Wc`` (nt, w, m) toggled values, ``af`` (nt, m), the
    per-point masked squared mass ``sum_j W[q, j]^2`` over each trial's
    toggled columns, and ``zero`` (nt, m), the points each trial's
    zero-toggle leaves exactly zero.  Sentinel slots gather a real column
    and are masked to zero — column gathers touch only O(nt·w·m) values,
    so no (n, m) transpose or padded copy of the full matrix is ever
    built.
    """
    cid = jnp.minimum(cols, n - 1)
    valid = (cols < n).astype(Wd.dtype)                 # (nt, w)
    Wc = jnp.transpose(Wd[:, cid], (1, 2, 0)) * valid[:, :, None]
    af = (Ad[:, cid] * valid[None, :, :]).sum(axis=2).T
    zero = nnz[None, :] == (Wc != 0).sum(axis=1)
    return Wc, af, zero


@functools.partial(jax.jit, static_argnames=("frac", "fixed", "ct"),
                   donate_argnums=(8, 9, 10))
def _round(Wc, af, zero, sq, rcache, sidx, p, active, labels, ncl,
           used_thr, *, frac, fixed, ct):
    """One lockstep greedy round for every active trial — the exact
    device mirror of the host ``_batch_round`` semantics.

    For a zero-toggle the D² row of seed p under trial t is the base row
    plus ``-(af_t[q] + af_t[p] - 2 * sum_j W[q,j] W[p,j])`` (only toggled
    columns j contribute), and the trial's squared seed norm drops by
    ``af_t[p]`` — both O(w) per point, fused here with the
    threshold/candidacy/assignment phase *and* the next round's seed
    selection into a single dispatch (the driver pulls only the 2·nt
    scalars of next seeds/activity per round).
    A seed that the toggle leaves exactly zero (``zero``) takes the
    zero-seed rule of the host ``_greedy_cluster``: norm 0, and exactly
    the zero points within a zero threshold — the residue of the deltas
    decides nothing.
    ``labels``/``ncl``/``used_thr`` are donated: each round writes the
    next round's state into the buffers of the last.
    """
    nt, m = labels.shape
    R = rcache[sidx]                                       # (nt, m)
    wp = jnp.take_along_axis(Wc, p[:, None, None], axis=2)  # (nt, w, 1)
    b = (Wc * wp).sum(axis=1)                              # (nt, m)
    afp = jnp.take_along_axis(af, p[:, None], axis=1)      # (nt, 1)
    # No zero clamp: candidacy compares against thr² >= 0, so negative
    # roundoff residue decides identically to the clamped row.
    rows = R - (af + afp - 2.0 * b)
    zp = jnp.take_along_axis(zero, p[:, None], axis=1)     # (nt, 1)
    if fixed is None:
        sqp = jnp.where(zp[:, 0], 0.0,
                        jnp.maximum(sq[p] - afp[:, 0], 0.0))
        thr = frac * jnp.sqrt(sqp)
    else:
        thr = jnp.full((nt,), fixed, rows.dtype)
    used_thr = jnp.where(active, jnp.maximum(used_thr, thr), used_thr)
    near = rows <= (thr * thr)[:, None]
    near = jnp.where(zp, zero | (near & (thr > 0)[:, None]), near)
    cand = (labels < 0) & near
    # cand includes the seed itself on every active trial (its own row
    # entry is exactly 0), so the neighbour count is the sum minus one —
    # cheaper than scattering the seed column out of cand.
    grow = active & (cand.sum(axis=1) - 1 >= ct)
    seed = active[:, None] & (jnp.arange(m)[None, :] == p[:, None])
    labels = jnp.where((grow[:, None] & cand) | seed, ncl[:, None], labels)
    ncl = ncl + active.astype(ncl.dtype)
    unass = labels < 0
    p_next = jnp.argmax(unass, axis=1).astype(jnp.int32)
    active_next = unass.any(axis=1)
    return labels, ncl, used_thr, p_next, active_next


class DeviceLockstep:
    """Per-state device twin: owns the sentinel-padded device matrices
    and the persistent device row cache, and runs eligible
    ``cluster_batch`` calls as lockstep device rounds."""

    def __init__(self, backend, handle, threshold, threshold_frac,
                 count_threshold, fetch_stats: Dict):
        self._backend = backend
        self._handle = handle
        Wd, sqd = backend.device_arrays(handle)
        self._m, self._n = int(Wd.shape[0]), int(Wd.shape[1])
        self._Wd = Wd
        self._Ad = Wd * Wd
        self._nnz = jnp.count_nonzero(Wd, axis=1).astype(jnp.int32)
        self._sqd = sqd
        self._fixed = None if threshold is None else float(threshold)
        self._frac = float(threshold_frac)
        self._ct = int(count_threshold)
        self._stats = fetch_stats
        # device row cache: seed -> slot in the (capacity, m) cache;
        # capacity doubles so recompiles of _round stay O(log seeds).
        self._slot: Dict[int, int] = {}
        self._rcache = None
        self._used = 0

    # -- row cache ---------------------------------------------------------
    def _ensure_rows(self, seeds: Sequence[int]) -> None:
        """Fetch (one batched backend call) the base D² rows of every
        seed not yet cached; fetched rows stay device-resident for the
        lifetime of the state."""
        missing = [q for q in seeds if q not in self._slot]
        if not missing:
            return
        rows = self._backend.device_rows(
            self._handle, np.asarray(missing, dtype=np.int32))
        st = self._stats
        st["calls"] += 1
        st["device_calls"] += 1
        st["rows"] += len(missing)
        for q in missing:
            st["per_seed"][q] = st["per_seed"].get(q, 0) + 1
        need = self._used + len(missing)
        cap = 0 if self._rcache is None else int(self._rcache.shape[0])
        if need > cap:
            newcap = max(cap * 2, 8)
            while newcap < need:
                newcap *= 2
            base = jnp.zeros((newcap, self._m), rows.dtype)
            if self._rcache is not None:
                base = jax.lax.dynamic_update_slice(base, self._rcache,
                                                    (0, 0))
            self._rcache = base
        self._rcache = jax.lax.dynamic_update_slice(self._rcache, rows,
                                                    (self._used, 0))
        for q in missing:
            self._slot[q] = self._used
            self._used += 1

    # -- lockstep driver ---------------------------------------------------
    def cluster_batch(self, cols_l: List[List[int]]):
        """Run every trial (each a zero-toggle of ``cols_l[t]`` on the
        base matrix) to completion in lockstep device rounds.  Returns
        ``(labels, n_clusters, used_thresholds)`` host arrays of shape
        (nt, m)/(nt,)/(nt,)."""
        nt = len(cols_l)
        m = self._m
        # Pad the trial axis to a power of two (dummies replicate trial
        # 0, adding no seeds and no rounds) and the toggle width to a
        # power of two of sentinel columns, so jit traces stay bounded
        # by O(log) distinct shapes per (m, n).
        w = max(1, max((len(c) for c in cols_l), default=1))
        wpad = 1 << (w - 1).bit_length()
        ntp = 1 << (nt - 1).bit_length()
        cols = np.full((ntp, wpad), self._n, dtype=np.int32)
        for t, cl in enumerate(cols_l):
            cols[t, :len(cl)] = cl
        cols[nt:] = cols[0]
        Wc, af, zero = _prep(self._Wd, self._Ad, self._nnz,
                             jnp.asarray(cols), n=self._n)
        labels = jnp.full((ntp, m), -1, jnp.int32)
        ncl = jnp.zeros((ntp,), jnp.int32)
        used_thr = jnp.full((ntp,), -1.0, jnp.float32)
        # All labels start unassigned, so round 1's seeds are known
        # without a device round-trip: point 0, every trial active.
        p_h = np.zeros(ntp, dtype=np.int32)
        act_h = np.ones(ntp, dtype=bool)
        p, active = jnp.asarray(p_h), jnp.asarray(act_h)
        while True:
            seeds = sorted({int(q) for q, a in zip(p_h, act_h) if a})
            with span("lockstep.round", trials=int(act_h.sum()),
                      seeds=len(seeds)):
                self._ensure_rows(seeds)
                sidx = np.zeros(ntp, dtype=np.int32)
                for t in np.nonzero(act_h)[0]:
                    sidx[t] = self._slot[int(p_h[t])]
                labels, ncl, used_thr, p, active = _round(
                    Wc, af, zero, self._sqd, self._rcache, jnp.asarray(sidx),
                    p, active, labels, ncl, used_thr,
                    frac=self._frac, fixed=self._fixed, ct=self._ct)
                with span("clustering.device_wait", site="lockstep"):
                    p_h = np.asarray(p)
                    act_h = np.asarray(active)
            if not act_h.any():
                break
        # Labels stay int32 — every consumer (same_partition, bincount,
        # members) is dtype-agnostic, and the int64 upcast would double
        # the pull cost at fleet shapes.
        with span("clustering.device_wait", site="lockstep"):
            return (np.asarray(labels)[:nt], np.asarray(ncl[:nt]),
                    np.asarray(used_thr[:nt]))
