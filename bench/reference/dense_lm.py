"""The served model's weights, drawn from the seed, and its plain float32
reference forward pass, importing nothing of the program.

Weights: every leaf is ``std * N(0, 1)`` drawn with the key
``fold_in(fold_in(key(seed), leaf), layer)`` and stored in bfloat16, the
type it is served in.  :func:`init_params` makes them all on the device in
one jitted call, stacked per layer in the layout the program's dense
transformer takes; :func:`layer_weights` draws one layer's again, bit for
bit, so the reference never holds more than one layer.

Reference (H2O-Danube3, arXiv:2407.09276; Llama-style blocks): token
embedding; per layer, RMSNorm with weight ``1 + w``, grouped-query
attention with rotate-half rotary positions, causal and within the sliding
window, then RMSNorm and a SwiGLU MLP, each added to the residual; final
RMSNorm and an untied output head.  Float32 throughout, matrix products at
``Precision.HIGHEST``.  ``quant="int8"`` or ``"fp8"`` is the control:
every matrix product with int8 (or float8 e4m3) weights, scaled per output
channel, and activations, scaled per token: the lower precision a faster
path would be tempted to take.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

HIGHEST = "highest"


def dims(c: Dict[str, Any]):
    d, H = c["hidden_size"], c["num_attention_heads"]
    return (c["num_hidden_layers"], d, H, c["num_key_value_heads"],
            c.get("head_dim") or d // H, c["intermediate_size"],
            c["vocab_size"])


def leaves(c: Dict[str, Any]) -> List[Tuple[str, Tuple[int, ...], float, bool]]:
    """(path, per-layer shape, std, stacked per layer) of every weight."""
    L, d, H, KV, dh, ff, V = dims(c)
    return [
        ("embed/tokens", (V, d), 1.0, False),
        ("layers/ln1", (d,), 0.1, True),
        ("layers/ln2", (d,), 0.1, True),
        ("layers/attn/wq", (d, H, dh), d ** -0.5, True),
        ("layers/attn/wk", (d, KV, dh), d ** -0.5, True),
        ("layers/attn/wv", (d, KV, dh), d ** -0.5, True),
        ("layers/attn/wo", (H, dh, d), (H * dh) ** -0.5, True),
        ("layers/mlp/wi", (d, ff), d ** -0.5, True),
        ("layers/mlp/wg", (d, ff), d ** -0.5, True),
        ("layers/mlp/wo", (ff, d), ff ** -0.5, True),
        ("final_norm", (d,), 0.1, False),
        ("head", (d, V), d ** -0.5, False),
    ]


def _key(seed: int):
    import jax
    return jax.random.fold_in(jax.random.key(seed % 2 ** 32), seed // 2 ** 32)


def _draw(key, leaf: int, layer, shape, std):
    import jax
    import jax.numpy as jnp
    k = jax.random.fold_in(jax.random.fold_in(key, leaf), layer)
    return (std * jax.random.normal(k, shape, jnp.float32)).astype(
        jnp.bfloat16)


def init_params(c: Dict[str, Any], seed: int):
    """All weights, bfloat16, on the device, in one jitted call."""
    import jax
    import jax.numpy as jnp

    L = c["num_hidden_layers"]
    spec = leaves(c)

    def make(key):
        tree: Dict[str, Any] = {}
        for i, (path, shape, std, stacked) in enumerate(spec):
            if stacked:
                v = jax.vmap(lambda l, i=i, s=shape, sd=std:
                             _draw(key, i, l, s, sd))(jnp.arange(L))
            else:
                v = _draw(key, i, 0, shape, std)
            node = tree
            *head, last = path.split("/")
            for p in head:
                node = node.setdefault(p, {})
            node[last] = v
        return tree

    return jax.jit(make)(_key(seed))


@functools.lru_cache(maxsize=None)
def _layer_fn(spec):
    import jax
    import jax.numpy as jnp

    def fn(key, layer):
        return {path.split("/", 1)[1]: _draw(key, i, layer, shape, std)
                .astype(jnp.float32)
                for i, (path, shape, std, stacked) in enumerate(spec)
                if stacked}
    return jax.jit(fn)


def layer_weights(c: Dict[str, Any], seed: int, layer: int):
    """Layer ``layer``'s weights as served (bfloat16), in float32."""
    import jax.numpy as jnp
    spec = tuple(leaves(c))
    return _layer_fn(spec)(_key(seed), jnp.int32(layer))


def other_weights(c: Dict[str, Any], seed: int):
    """The embedding, final norm and head as served, in float32."""
    import jax
    import jax.numpy as jnp
    spec = leaves(c)
    key = _key(seed)
    return {path: jax.jit(lambda k, i=i, s=shape, sd=std:
                          _draw(k, i, 0, s, sd).astype(jnp.float32))(key)
            for i, (path, shape, std, stacked) in enumerate(spec)
            if not stacked}


# -- the forward pass ---------------------------------------------------------
def _quant_int8(x, axis):
    """Symmetric int8 rounding of ``x`` with one scale per slice along
    ``axis`` (the reduced axis), returned dequantized."""
    import jax.numpy as jnp
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _quant_fp8(x, axis):
    """float8 (e4m3) rounding of ``x`` scaled per slice along ``axis`` to
    the format's largest value, returned dequantized."""
    import jax.numpy as jnp
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


QUANT = {"int8": _quant_int8, "fp8": _quant_fp8}


def _mm(spec: str, x, w, quant: str):
    import jax.numpy as jnp
    if quant in QUANT:
        x = QUANT[quant](x, -1)
        w = QUANT[quant](w, 0)
    return jnp.einsum(spec, x, w, precision=HIGHEST)


def _rms(x, w, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + w)


def _rope(x, pos, theta):
    import jax.numpy as jnp
    dh = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.lru_cache(maxsize=None)
def _block_fn(cfg_key, quant: str):
    import jax
    import jax.numpy as jnp
    c = dict(cfg_key)
    L, d, H, KV, dh, ff, V = dims(c)
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    window = c.get("sliding_window")

    def block(x, w):
        S = x.shape[0]
        pos = jnp.arange(S)
        h = _rms(x, w["ln1"], eps)
        q = _rope(_mm("sd,dk->sk", h, w["attn/wq"].reshape(d, H * dh),
                      quant).reshape(S, H, dh), pos, theta)
        k = _rope(_mm("sd,dk->sk", h, w["attn/wk"].reshape(d, KV * dh),
                      quant).reshape(S, KV, dh), pos, theta)
        v = _mm("sd,dk->sk", h, w["attn/wv"].reshape(d, KV * dh),
                quant).reshape(S, KV, dh)
        g = H // KV
        k = jnp.repeat(k, g, axis=1)
        v = jnp.repeat(v, g, axis=1)
        scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) \
            / np.sqrt(dh)
        mask = pos[None, :] <= pos[:, None]
        if window:
            mask &= pos[None, :] > pos[:, None] - window
        scores = jnp.where(mask[None], scores, -jnp.inf)
        att = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", att, v, precision=HIGHEST)
        x = x + _mm("sk,kd->sd", o.reshape(S, H * dh),
                    w["attn/wo"].reshape(H * dh, d), quant)
        h = _rms(x, w["ln2"], eps)
        a = jax.nn.silu(_mm("sd,df->sf", h, w["mlp/wg"], quant)) \
            * _mm("sd,df->sf", h, w["mlp/wi"], quant)
        return x + _mm("sf,fd->sd", a, w["mlp/wo"], quant)

    return jax.jit(block)


@functools.lru_cache(maxsize=None)
def _head_fn(cfg_key, quant: str):
    import jax
    import jax.numpy as jnp
    eps = dict(cfg_key)["rms_norm_eps"]

    def head(x, norm, w, tokens, valid):
        """Per position: the reference's best logit less the logit of the
        token that follows (the served one), and the index of the best."""
        logits = _mm("sd,dv->sv", _rms(x, norm, eps), w, quant)
        best = jnp.max(logits, axis=-1)
        nxt = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
        return jnp.where(valid, best - nxt, 0.0), jnp.argmax(logits, -1)

    return jax.jit(head)


def _cfg_key(c):
    return tuple(sorted((k, v) for k, v in c.items()
                        if isinstance(v, (int, float, str, type(None)))))


def forward_gaps(c: Dict[str, Any], seed: int,
                 sequences: Sequence[np.ndarray], scored: Sequence[range],
                 length: int, quants: Sequence[str] = ()):
    """Per sequence, the float32 reference's best logit less its logit of
    the token that follows, at each position in ``scored`` (zero
    elsewhere); and for each lower precision in ``quants``, the same gap of
    the token that precision puts first.  Returns ``{"none": [gaps per
    sequence], q: [gaps per sequence], ...}``.  Sequences are padded to
    ``length`` at the end; the causal mask keeps padding out of every
    scored position.  Runs layer by layer, one sequence at a time."""
    import jax.numpy as jnp

    L = c["num_hidden_layers"]
    ck = _cfg_key(c)
    other = other_weights(c, seed)
    toks = [np.pad(s, (0, length - len(s))) for s in sequences]
    modes = ("none",) + tuple(quants)
    xs = {q: [other["embed/tokens"][jnp.asarray(t)] for t in toks]
          for q in modes}
    for layer in range(L):
        w = layer_weights(c, seed, layer)
        for q in modes:
            blk = _block_fn(ck, q)
            xs[q] = [blk(x, w) for x in xs[q]]
        del w
    valid = []
    for rg in scored:
        v = np.zeros(length, bool)
        v[list(rg)] = True
        valid.append(jnp.asarray(v))
    head = _head_fn(ck, "none")
    out = {"none": [np.asarray(head(x, other["final_norm"], other["head"],
                                    jnp.asarray(np.roll(t, -1)), v)[0])
                    for x, t, v in zip(xs["none"], toks, valid)]}
    for q in quants:
        qhead = _head_fn(ck, q)
        out[q] = []
        for x, xq, v in zip(xs["none"], xs[q], valid):
            _, first = qhead(xq, other["final_norm"], other["head"],
                             jnp.zeros(length, jnp.int32), v)
            out[q].append(np.asarray(head(x, other["final_norm"],
                                          other["head"], first, v)[0]))
    return out
