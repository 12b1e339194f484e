"""Logical-axis sharding rules (MaxText-style), DESIGN.md §6.

Params carry *logical* axis names (assigned at init); rules map logical
names to mesh axes.  ``sharding_for`` verifies divisibility and silently
drops a mesh axis that does not divide the dim (e.g. seamless' vocab 256206
on a 16-way model axis), so every (arch × mesh) pair lowers.

Parallelism encoding:
  * FSDP/ZeRO-3: 'embed' -> 'data' (params + optimizer state sharded over
    the data axis; XLA inserts per-layer all-gathers / reduce-scatters);
  * TP: 'vocab'/'heads'/'mlp' -> 'model';
  * EP: 'expert' -> 'model' (deepseek) or None + TP inside experts (mixtral);
  * DP: activation 'batch' -> ('pod', 'data');
  * SP: activation 'seq' -> 'data' for the long-context cells.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Rules = Dict[str, Union[None, str, Tuple[str, ...]]]

# -- parameter rules --------------------------------------------------------
PARAM_RULES: Rules = {
    "vocab": "model",
    "embed": "data",          # FSDP / ZeRO-3
    "heads": "model",
    "kv_heads": None,         # kv head counts (1..16) rarely divide 16
    "head_dim": None,
    "mlp": "model",
    "mlp2": None,
    "expert": "model",        # EP (overridden to None for 'tp' MoE sharding)
    "expert_r": None,
    "kv_lora": None,
    "lora": None,
    "conv": None,
    "heads_x_dim": "model",   # rwkv fused (d, d) projections
    "layers": None,           # scan dim
}

# -- activation rules --------------------------------------------------------
ACT_RULES: Rules = {
    "batch": ("pod", "data"),
    "seq": None,
    "act_embed": None,
    "act_heads": "model",
    "act_kv": "model",        # KV-cache head dim (decode memory fit)
    "act_mlp": "model",
    "expert": "model",
    "vocab_out": "model",
}


# Decode (weight-stationary) parameter rules: weights are fully sharded
# over data x model and STAY sharded — a decode step must not all-gather
# weights the way FSDP training does (per-token gather of the whole model);
# the contractions over sharded dims cost only tiny (B,1,·) activation
# all-reduces.  §Perf decode iterations.
DECODE_PARAM_RULES: Rules = {
    "vocab": ("data", "model"),
    "embed": None,
    "heads": "model",
    "kv_heads": None,
    "head_dim": "data",
    "mlp": ("data", "model"),
    "mlp2": None,
    "expert": "model",
    "expert_r": None,
    "kv_lora": "data",
    "lora": None,
    "conv": None,
    "heads_x_dim": ("data", "model"),
    "layers": None,
}


def rules_for(cfg, *, param: bool = True, seq_sharded: bool = False,
              sp: bool = False, decode: bool = False) -> Rules:
    """``seq_sharded``: long-context cells shard seq over 'data' (batch=1).
    ``sp``: sequence parallelism — residual-stream activations between
    blocks live seq-sharded over the *model* axis (Korthikanti-style), so
    the per-layer saved activations shrink by the TP degree; the qkv/mlp
    matmuls all-gather the sequence just-in-time (bf16, half the bytes of
    the f32 partial-sum all-reduces they replace).  §Perf iteration."""
    if param and decode:
        rules = dict(DECODE_PARAM_RULES)
    else:
        rules = dict(PARAM_RULES if param else ACT_RULES)
    if param and getattr(cfg, "moe", None) is not None:
        if cfg.moe.sharding == "tp":
            rules["expert"] = None
    if not param and seq_sharded:
        rules["seq"] = "data"
        rules["batch"] = None
    elif not param and sp:
        rules["seq"] = "model"
    return rules


def _axes_of(mesh: Mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def spec_for(shape: Sequence[int], axes: Sequence[Optional[str]],
             rules: Rules, mesh: Mesh) -> P:
    """Build a PartitionSpec; drop mesh axes that don't divide the dim."""
    mesh_sizes = _axes_of(mesh)
    parts = []
    used: set = set()
    for dim, name in zip(shape, axes):
        target = rules.get(name) if name else None
        if target is None:
            parts.append(None)
            continue
        cand = (target,) if isinstance(target, str) else tuple(target)
        cand = tuple(a for a in cand if a in mesh_sizes and a not in used)
        size = int(np.prod([mesh_sizes[a] for a in cand])) if cand else 1
        while cand and dim % size != 0:
            cand = cand[:-1]
            size = int(np.prod([mesh_sizes[a] for a in cand])) if cand else 1
        if not cand:
            parts.append(None)
        else:
            used.update(cand)
            parts.append(cand if len(cand) > 1 else cand[0])
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def sharding_for(shape, axes, rules: Rules, mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, spec_for(shape, axes, rules, mesh))


def tree_shardings(shapes_tree, axes_tree, rules: Rules, mesh: Mesh):
    """Map matching (shapes, axes) trees to NamedShardings.  ``shapes_tree``
    leaves are ShapeDtypeStruct/arrays; ``axes_tree`` leaves are tuples of
    logical names (or None)."""
    def one(leaf, axes):
        if axes is None:
            return NamedSharding(mesh, P())
        return sharding_for(leaf.shape, axes, rules, mesh)

    return jax.tree.map(one, shapes_tree, axes_tree,
                        is_leaf=lambda x: x is None or isinstance(x, tuple))


# -- activation-constraint context ------------------------------------------
class _Ctx(threading.local):
    mesh: Optional[Mesh] = None
    rules: Optional[Rules] = None


_CTX = _Ctx()


@contextlib.contextmanager
def activation_sharding(mesh: Mesh, rules: Rules):
    """While active, :func:`constrain` inserts with_sharding_constraint."""
    prev = (_CTX.mesh, _CTX.rules)
    _CTX.mesh, _CTX.rules = mesh, rules
    try:
        yield
    finally:
        _CTX.mesh, _CTX.rules = prev


def constrain(x, axes: Sequence[Optional[str]]):
    """Constrain an activation to the current context's sharding (no-op
    outside a context, so smoke tests on 1 device are unaffected)."""
    if _CTX.mesh is None or _CTX.rules is None:
        return x
    spec = spec_for(x.shape, axes, _CTX.rules, _CTX.mesh)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(_CTX.mesh, spec))
