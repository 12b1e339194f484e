"""Operations and bytes of the benchmark's kernels, from shapes alone, and
the table of peaks they are held against.

Nothing here reads what the compiler made of a program: the counts are
what the algorithm needs, so a change to the program cannot move them.
"""
from __future__ import annotations

import os
from typing import Any, Dict

from .harness import BENCH_DIR, load_json

PEAKS_FILE = os.path.join(BENCH_DIR, "peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    """Peaks of one chip of ``device_kind``; a device not in the table is
    an error, never a default."""
    table = load_json(PEAKS_FILE)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE}; known: {sorted(table)}")
    return {k: float(v) for k, v in table[device_kind].items()}


def roofline_s(flops: float, nbytes: float, pk: Dict[str, float]) -> float:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bytes/s."""
    return max(flops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])


# -- squared-distance rows (the analyzer's clustering kernel) ----------------
def d2_rows(m: int, n: int, rows: int) -> Dict[str, float]:
    """``rows`` seed rows of squared distances to all ``m`` points in R^n
    (Gram form |a|^2 + |b|^2 - 2ab): m·n multiply-adds and 3·m combines per
    row; the float32 points and their norms read at least once, each row
    written once."""
    flops = float(rows) * (2.0 * m * n + 3.0 * m)
    nbytes = 4.0 * (m * n + m) + 4.0 * m * rows
    return {"flops": flops, "bytes": nbytes}


# -- dense GQA transformer (the served model) -------------------------------
def _dims(cfg: Dict[str, Any]):
    d = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    KV = cfg["num_key_value_heads"]
    dh = cfg.get("head_dim") or d // H
    return (cfg["num_hidden_layers"], d, H, KV, dh, cfg["intermediate_size"],
            cfg["vocab_size"])


def layer_params(cfg: Dict[str, Any]) -> int:
    """Matrix parameters of one layer: q, k, v, o and the gated MLP."""
    L, d, H, KV, dh, ff, V = _dims(cfg)
    return d * H * dh + 2 * d * KV * dh + H * dh * d + 3 * d * ff


def token_flops(cfg: Dict[str, Any], pos: int) -> float:
    """Forward operations of the token at position ``pos`` (0-based):
    every matrix of every layer and the output head, and causal attention
    over its ``pos + 1`` keys (scores and weighted values)."""
    L, d, H, KV, dh, ff, V = _dims(cfg)
    return (2.0 * L * layer_params(cfg) + 2.0 * d * V
            + 4.0 * L * H * dh * (pos + 1))


def span_flops(cfg: Dict[str, Any], start: int, k: int) -> float:
    """Operations of the tokens at positions ``start .. start + k - 1``."""
    L, d, H, KV, dh, ff, V = _dims(cfg)
    keys = k * start + k * (k + 1) / 2.0      # sum of (pos + 1)
    return (k * (2.0 * L * layer_params(cfg) + 2.0 * d * V)
            + 4.0 * L * H * dh * keys)


def decode_bytes(cfg: Dict[str, Any], pos: int, itemsize: int = 2) -> float:
    """Bytes one decode call at position ``pos`` must move: every weight
    but the embedding table (of which one row), the norms, the keys and
    values of the ``pos`` earlier positions, and the new position's keys
    and values written."""
    L, d, H, KV, dh, ff, V = _dims(cfg)
    weights = L * (layer_params(cfg) + 2 * d) + d + d * V + d
    kv = 2 * L * KV * dh
    return float(itemsize * (weights + kv * pos + kv))
