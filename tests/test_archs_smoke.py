"""Per-architecture smoke tests: every assigned arch instantiates a reduced
same-family config and runs one forward/train step on CPU, asserting output
shapes and the absence of NaNs (assignment §ARCHITECTURES)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, list_archs, shapes_for
from repro.data import batch_for_model
from repro.models import build
from repro.optim import AdamWConfig
from repro.train import make_train_step

ARCHS = [a for a in list_archs() if a != "st-100m"]


def _batch(cfg, B=2, S=32):
    key = jax.random.key(1)
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": toks}
    if cfg.family in ("vlm", "encdec") and cfg.frontend:
        batch["embeds"] = jax.random.normal(
            key, (B, cfg.frontend_tokens, cfg.d_model), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_and_finite(arch):
    cfg = get_arch(arch).smoke
    api = build(cfg)
    params, axes = api.init(jax.random.key(0))
    batch = _batch(cfg)
    logits, info = jax.jit(lambda p, b: api.forward(
        p, b["tokens"], embeds=b.get("embeds")))(params, batch)
    B, S = batch["tokens"].shape
    S_total = S + (cfg.frontend_tokens if cfg.family == "vlm" else 0)
    assert logits.shape == (B, S_total, cfg.vocab)
    assert np.isfinite(np.asarray(logits)).all()


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_runs_and_is_finite(arch):
    cfg = get_arch(arch).smoke
    api = build(cfg)
    params, _ = api.init(jax.random.key(0))
    from repro.optim import init_opt_state
    opt = init_opt_state(params)
    step = jax.jit(make_train_step(cfg, AdamWConfig(lr=1e-3)))
    new_params, new_opt, metrics = step(params, opt, _batch(cfg))
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    assert int(new_opt["step"]) == 1
    # params actually changed
    diff = sum(float(jnp.abs(a - b).sum()) for a, b in zip(
        jax.tree.leaves(params), jax.tree.leaves(new_params)))
    assert diff > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_step(arch):
    cfg = get_arch(arch).smoke
    api = build(cfg)
    params, _ = api.init(jax.random.key(0))
    B = 2
    kw = {"enc_len": 8} if cfg.family == "encdec" else {}
    state = api.init_decode_state(B, 16, **kw)
    step = jax.jit(lambda p, s, t, pos: api.decode_step(p, s, t, pos))
    tok = jnp.zeros((B, 1), jnp.int32)
    for pos in range(4):
        logits, state = step(params, state, tok, jnp.int32(pos))
        tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
    assert logits.shape == (B, 1, cfg.vocab)
    assert np.isfinite(np.asarray(logits)).all()


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_matches_assignment(arch):
    """The FULL config carries the exact published numbers."""
    expected = {
        "chatglm3-6b": (28, 4096, 32, 2, 13696, 65024),
        "h2o-danube-3-4b": (24, 3840, 32, 8, 10240, 32000),
        "mistral-nemo-12b": (40, 5120, 32, 8, 14336, 131072),
        "gemma-7b": (28, 3072, 16, 16, 24576, 256000),
        "phi-3-vision-4.2b": (32, 3072, 32, 32, 8192, 32064),
        "deepseek-v2-lite-16b": (27, 2048, 16, 16, 10944, 102400),
        "mixtral-8x22b": (56, 6144, 48, 8, 16384, 32768),
        "rwkv6-3b": (32, 2560, 40, 40, 8960, 65536),
        "seamless-m4t-medium": (12, 1024, 16, 16, 4096, 256206),
        "recurrentgemma-9b": (38, 4096, 16, 1, 12288, 256000),
    }
    L, d, H, KV, ff, V = expected[arch]
    cfg = get_arch(arch).full
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.d_ff, cfg.vocab) == (L, d, H, KV, ff, V)


@pytest.mark.parametrize("arch", ARCHS)
def test_shape_assignment(arch):
    cfg = get_arch(arch).full
    names = [s.name for s in shapes_for(cfg)]
    assert {"train_4k", "prefill_32k", "decode_32k"} <= set(names)
    if cfg.family in ("ssm", "hybrid"):
        assert "long_500k" in names     # sub-quadratic archs run 500k
    else:
        assert "long_500k" not in names  # skipped per DESIGN.md §5


def test_moe_family_flags():
    assert get_arch("mixtral-8x22b").full.moe.n_experts == 8
    assert get_arch("mixtral-8x22b").full.moe.top_k == 2
    ds = get_arch("deepseek-v2-lite-16b").full
    assert ds.moe.top_k == 6 and ds.moe.n_shared == 2
    assert ds.mla.kv_lora_rank == 512


def test_gemma_head_dim():
    cfg = get_arch("gemma-7b").full
    assert cfg.resolved_head_dim == 256
    assert cfg.scale_embed and cfg.tie_embeddings
