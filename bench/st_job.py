"""The paper's ST job as data: its fine-grain region tree and per-region
behaviour (copied from the program's ``scenarios/st.py`` into
``configs/st-fine-m2048.json``), tiled to a deployment's rank count, and
the per-step samples a collector would record for it.

Samples follow the program's synthetic collector: each (region, step)
draws one multiplicative measurement noise per rank, ``1 + jitter * N(0,
1)``; times and operations scale with it, rates do not.
"""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

METRICS = ("wall_time", "cpu_time", "flops", "bytes", "vmem_pressure",
           "hbm_intensity", "comm_time", "comm_bytes", "host_bytes")


def schema(config: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The region tree in pre-order, root first, as a trace header holds
    it."""
    out = [{"id": 0, "name": config["root"], "parent": None,
            "management": False}]
    out += [{"id": rid, "name": name, "parent": parent, "management": False}
            for rid, name, parent in config["regions"]]
    return out


def region_ids(config: Dict[str, Any]) -> List[int]:
    return sorted(int(r) for r in config["behaviours"])


def rank_scales(config: Dict[str, Any], traffic: Dict[str, Any]
                ) -> Dict[int, np.ndarray]:
    """Per-rank multiplier of each region's time and operations: its
    profile tiled over the ranks, or the profile's mean for every rank
    where the traffic balances it (the paper's dynamic-dispatch fix)."""
    m = int(config["n_processes"])
    balanced = set(traffic.get("balanced_profiles", ()))
    out = {}
    for rid, b in config["behaviours"].items():
        imb = b.get("imbalance")
        if imb is None:
            s = np.ones(m)
        else:
            prof = np.asarray(config["profiles"][imb["profile"]], np.float64)
            if imb["profile"] in balanced:
                prof = np.full(prof.size, prof.mean())
            if m % prof.size:
                raise ValueError(f"{m} ranks do not tile a profile of "
                                 f"{prof.size}")
            s = np.tile(prof, m // prof.size) * imb.get("scale", 1.0) \
                + imb.get("offset", 0.0)
        out[int(rid)] = s
    return out


def window_data(config: Dict[str, Any], traffic: Dict[str, Any],
                rng: np.random.Generator, n_steps: int
                ) -> Dict[str, np.ndarray]:
    """``{metric: (n_steps, 1, m, n)}`` samples of ``n_steps`` steps, columns
    in :func:`region_ids` order."""
    m = int(config["n_processes"])
    rids = region_ids(config)
    scales = rank_scales(config, traffic)
    jitter = float(config["jitter"])
    data = {k: np.zeros((n_steps, 1, m, len(rids))) for k in METRICS}
    for j, rid in enumerate(rids):
        b = config["behaviours"][str(rid)]
        scale = scales[rid]
        noise = 1.0 + jitter * rng.standard_normal((n_steps, m))
        t = b["base_time"] * scale * noise
        data["wall_time"][:, 0, :, j] = t
        data["cpu_time"][:, 0, :, j] = t
        data["flops"][:, 0, :, j] = t * b["flops_per_s"]
        data["bytes"][:, 0, :, j] = t * b["flops_per_s"] * b["hbm_intensity"]
        data["vmem_pressure"][:, 0, :, j] = b["vmem_pressure"]
        data["hbm_intensity"][:, 0, :, j] = b["hbm_intensity"]
        data["host_bytes"][:, 0, :, j] = b.get("host_bytes", 0.0) * scale
    return data
