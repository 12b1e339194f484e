"""Tiled Pallas kernels for batched pairwise-distance seed rows.

The AutoAnalyzer clustering core (``repro.core.clustering``) only ever
needs squared Euclidean distances from a handful of *seed* points to all
m points — never the full m×m matrix.  These kernels compute one
(seeds, block_m) output tile per grid step from the Gram identity

    D²[s, q] = |W_s|² + |W_q|² − 2·W_s·W_q

with the seed block resident in VMEM across the whole sweep and the
point matrix streamed through in ``block_m``-row tiles, so VMEM holds
O(seeds·n + block_m·n) floats regardless of m.  Compiled on a TPU
target; interpret mode elsewhere (same kernel body, correctness only).

Two entry points share one kernel body:

* :func:`multi_seed_rows` — the batched multi-seed call the lockstep
  trial rounds of ``IncrementalClusterState.cluster_batch`` issue: one
  pallas_call computes the rows of *all* unique seeds of a round.  The
  grid is (m_tiles, k_tiles) with the seed-tile axis innermost, so each
  point tile is streamed through VMEM **once** and reused across every
  seed tile (consecutive grid steps with an identical block index skip
  the re-copy); when ``block_k`` covers all seeds (the common case) the
  whole seed block simply stays resident.
* :func:`seed_rows` — the single-block legacy shape, now a thin wrapper
  that delegates to :func:`multi_seed_rows` with ``block_k`` covering
  the padded seed count, which reproduces the original single-tile
  numerics exactly.

Inputs are zero-padded to tile-friendly shapes (zero rows/columns
contribute nothing to the Gram product and padded output columns are
sliced off), so callers can pass any (m, n, k).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _kernel(ws_ref, sqs_ref, w_ref, sq_ref, o_ref):
    # HIGHEST: at default precision the TPU's matrix unit rounds f32
    # inputs to bf16, and the Gram identity's cancellation turns that into
    # D² errors of about a tenth of the clustering threshold² — enough to
    # move points near it across (tests/test_device_lockstep.py pins the
    # error).
    g = jnp.dot(ws_ref[...], w_ref[...].T,
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)
    d = sqs_ref[...] + sq_ref[...] - 2.0 * g
    o_ref[...] = jnp.maximum(d, 0.0)


@functools.partial(jax.jit,
                   static_argnames=("block_m", "block_k", "interpret"))
def multi_seed_rows(points, sq, idx, *, block_m: int = 512,
                    block_k: int = 256, interpret: bool = False):
    """Squared-distance rows of ``points[idx]`` against all points, for a
    whole batch of seeds in one pallas_call.

    points : (m, n) float32 device array.
    sq     : (m,) row squared norms of ``points``.
    idx    : (k,) int32 seed indices (one lockstep round's unique seeds).
    Returns (k, m) float32, clamped at zero.

    The grid is (m_tiles, k_tiles), seed tiles innermost: a point tile's
    block index only changes with the outer step, so Pallas keeps it in
    VMEM across the inner seed sweep — points are streamed exactly once
    regardless of how many seed tiles there are.
    """
    m, n = points.shape
    k = idx.shape[0]
    seeds = jnp.take(points, idx, axis=0)
    sqs = jnp.take(sq, idx)

    bk = _round_up(max(min(block_k, k), 8), 8)
    kp = _round_up(max(k, 8), bk)
    np_ = _round_up(max(n, 1), 128)
    bm = min(block_m, _round_up(max(m, 1), 128))
    mp = _round_up(max(m, 1), bm)

    seeds_p = jnp.zeros((kp, np_), points.dtype).at[:k, :n].set(seeds)
    sqs_p = jnp.zeros((kp, 1), points.dtype).at[:k, 0].set(sqs)
    points_p = jnp.zeros((mp, np_), points.dtype).at[:m, :n].set(points)
    sq_p = jnp.zeros((1, mp), points.dtype).at[0, :m].set(sq)

    out = pl.pallas_call(
        _kernel,
        grid=(mp // bm, kp // bk),
        in_specs=[
            pl.BlockSpec((bk, np_), lambda i, j: (j, 0)),
            pl.BlockSpec((bk, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((bm, np_), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bm), lambda i, j: (0, i)),
        ],
        out_specs=pl.BlockSpec((bk, bm), lambda i, j: (j, i)),
        out_shape=jax.ShapeDtypeStruct((kp, mp), points.dtype),
        interpret=interpret,
    )(seeds_p, sqs_p, points_p, sq_p)
    return out[:k, :m]


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def seed_rows(points, sq, idx, *, block_m: int = 512,
              interpret: bool = False):
    """Squared-distance rows of ``points[idx]`` against all points.

    points : (m, n) float32 device array.
    sq     : (m,) row squared norms of ``points``.
    idx    : (k,) int32 seed indices.
    Returns (k, m) float32, clamped at zero.

    Delegates to :func:`multi_seed_rows` with one seed tile covering the
    padded seed count — the padded shapes, grid walk and per-tile dot are
    exactly the original single-block kernel's, so existing callers see
    bit-identical float32 output.
    """
    k = int(idx.shape[0])
    return multi_seed_rows(points, sq, idx, block_m=block_m,
                           block_k=_round_up(max(k, 8), 8),
                           interpret=interpret)
