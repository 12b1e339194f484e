"""Operations and bytes of the served mixture-of-experts model with latent
attention (DeepSeek-V2-Lite at one chip's share), from shapes alone.

The least work a call needs, whatever implements it: every weight outside
the routed experts once; of the routed experts, those the call's tokens
are expected to reach among the held block, under uniform routing; the
latent cache prefix once.  Nothing here reads what the compiler made of a
program, and nothing comes from the program.

Attention operations: a prompt token decompresses its own key and value
from the latent (``r -> H (dn + dv)``) and attends at full rank over its
``pos + 1`` keys; a decoded token attends in latent space over the cache
(its query absorbed into ``wkv_b``'s key half, scores over the latent and
the rotary key, the weighted latent through the value half), the form
that needs no decompression of the cache.
"""
from __future__ import annotations

from typing import Any, Dict


def _dims(c: Dict[str, Any]):
    return (c["num_hidden_layers"], c["first_k_dense_replace"],
            c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"],
            c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"],
            c["intermediate_size"], c["moe_intermediate_size"],
            c["n_routed_experts_published"], c["n_routed_experts"],
            c["num_experts_per_tok"], c["n_shared_experts"], c["vocab_size"])


def attn_params(c: Dict[str, Any]) -> int:
    """Matrix parameters of one latent attention: wq, wkv_a, wkv_b, wo."""
    L, Ld, d, H, r, dn, dr, dv, ff, fe, E, held, k, ns, V = _dims(c)
    return (d * H * (dn + dr) + d * (r + dr) + r * H * (dn + dv)
            + H * dv * d)


def expert_params(c: Dict[str, Any]) -> int:
    """One routed expert: a SwiGLU of the expert width."""
    L, Ld, d, H, r, dn, dr, dv, ff, fe, E, held, k, ns, V = _dims(c)
    return 3 * d * fe


def shared_params(c: Dict[str, Any]) -> int:
    """Every matrix of an expert layer outside the routed experts: latent
    attention, router and shared experts."""
    L, Ld, d, H, r, dn, dr, dv, ff, fe, E, held, k, ns, V = _dims(c)
    return attn_params(c) + d * E + 3 * d * fe * ns


def dense_params(c: Dict[str, Any]) -> int:
    """Matrix parameters of one leading dense layer."""
    L, Ld, d, H, r, dn, dr, dv, ff, fe, E, held, k, ns, V = _dims(c)
    return attn_params(c) + 3 * d * ff


def experts_hit(c: Dict[str, Any], tokens: int) -> float:
    """Held experts that ``tokens`` tokens are expected to reach in one
    layer, each routing to k of the E experts uniformly:
    ``held * (1 - (1 - k/E) ** tokens)``."""
    L, Ld, d, H, r, dn, dr, dv, ff, fe, E, held, k, ns, V = _dims(c)
    return held * (1.0 - (1.0 - k / E) ** tokens)


def _token_matmuls(c: Dict[str, Any]) -> float:
    """Operations of one token's matrix products outside attention scores:
    every layer's weights it passes through (its expected share of the
    held experts, k held / E routes a layer) and the head."""
    L, Ld, d, H, r, dn, dr, dv, ff, fe, E, held, k, ns, V = _dims(c)
    routed = k * held / E * expert_params(c)
    return 2.0 * (Ld * dense_params(c)
                  + (L - Ld) * (shared_params(c) + routed) + d * V)


def prefill_flops(c: Dict[str, Any], pos: int) -> float:
    """Operations of a prompt token at position ``pos`` (0-based)."""
    L, Ld, d, H, r, dn, dr, dv, ff, fe, E, held, k, ns, V = _dims(c)
    return _token_matmuls(c) + 2.0 * L * H * (dn + dr + dv) * (pos + 1)


def decode_flops(c: Dict[str, Any], pos: int) -> float:
    """Operations of a decoded token at position ``pos``: its matrix
    products (the query's absorption into ``wkv_b``'s key half and the
    value up-projection count what the decompression of its own key and
    value would) and latent-space attention over ``pos + 1`` cached
    positions: scores over the latent and the rotary key, and the
    weighted latent."""
    L, Ld, d, H, r, dn, dr, dv, ff, fe, E, held, k, ns, V = _dims(c)
    return _token_matmuls(c) + 2.0 * L * H * (2 * r + dr) * (pos + 1)


def span_flops(c: Dict[str, Any], start: int, k: int) -> float:
    """Operations of the prompt tokens at positions ``start .. start + k -
    1``."""
    L, Ld, d, H, r, dn, dr, dv, ff, fe, E, held, kk, ns, V = _dims(c)
    keys = k * start + k * (k + 1) / 2.0      # sum of (pos + 1)
    return k * _token_matmuls(c) + 2.0 * L * H * (dn + dr + dv) * keys


def decode_bytes(c: Dict[str, Any], pos: int, itemsize: int = 2) -> float:
    """Bytes one decode call at position ``pos`` must move: every weight
    outside the routed experts (of the embedding, one row), the routed
    experts one token is expected to reach among the held, the norms, the
    latent and rotary key of the ``pos`` earlier positions, and the new
    position's written."""
    L, Ld, d, H, r, dn, dr, dv, ff, fe, E, held, k, ns, V = _dims(c)
    norms = L * (2 * d + r) + d
    weights = (Ld * dense_params(c) + (L - Ld) * (
        shared_params(c) + experts_hit(c, 1) * expert_params(c))
        + d * V + d + norms)
    cache = L * (r + dr)
    return float(itemsize * (weights + cache * pos + cache))
