"""DeepSeek-V2-Lite's mechanisms against the plain float32 reference
(``bench/reference/mla_moe_lm.py``) at smoke size: latent attention with
its latent decode path, YaRN, the leading dense layer, and the dropless
expert layer that holds a share of the experts; and the serving backend's
per-expert counts of the real routing."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import mla_moe_lm
from repro.configs import get_arch
from repro.models import build, layers
from repro.models.moe import moe_block

SEED = 2 ** 31 + 5
# Program (float32, this host's matrix products) against the reference
# (float32 at HIGHEST): the two differ only by the order of float32
# sums, a few 1e-6 on logits of order 4.  2e-4 leaves room for that and
# is an order of magnitude under what bfloat16 matmuls give (see
# test_bfloat16_falls_outside_the_tolerance).
ATOL = 2e-4


def smoke(held=0, first=0, dtype="float32"):
    cfg = get_arch("deepseek-v2-lite-16b").smoke
    return cfg.with_(moe=dataclasses.replace(cfg.moe, held=held,
                                             first_held=first),
                     dtype=dtype, param_dtype=dtype)


def ref_config(cfg):
    m, mo, y = cfg.mla, cfg.moe, cfg.yarn
    return {
        "num_hidden_layers": cfg.n_layers, "hidden_size": cfg.d_model,
        "num_attention_heads": cfg.n_heads, "kv_lora_rank": m.kv_lora_rank,
        "qk_nope_head_dim": m.nope_head_dim,
        "qk_rope_head_dim": m.rope_head_dim, "v_head_dim": m.v_head_dim,
        "intermediate_size": cfg.d_ff, "moe_intermediate_size": mo.d_ff,
        "n_routed_experts_published": mo.n_experts,
        "n_routed_experts": mo.n_held, "first_held_expert": mo.first_held,
        "num_experts_per_tok": mo.top_k, "n_shared_experts": mo.n_shared,
        "first_k_dense_replace": mo.first_dense, "vocab_size": cfg.vocab,
        "norm_topk_prob": mo.norm_topk_prob, "routed_scaling_factor": 1,
        "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
        "rope_scaling": {
            "factor": y.factor, "beta_fast": y.beta_fast,
            "beta_slow": y.beta_slow, "mscale": y.mscale,
            "mscale_all_dim": y.mscale_all_dim,
            "original_max_position_embeddings": y.original_max_positions},
    }


def weights(cfg, seed=SEED):
    """The reference's seeded weights, in the program's layout and type."""
    p = mla_moe_lm.init_params(ref_config(cfg), seed)
    return jax.tree.map(lambda x: x.astype(cfg.parameter_dtype()), p)


def served_logits(cfg, params, toks, chunk=8, prompt=24):
    """Logits of every position: the prompt prefilled in chunks into the
    cache, then one token at a time through the latent decode path."""
    api = build(cfg)
    step = jax.jit(lambda p, s, t, pos: api.decode_step(p, s, t, pos))
    state = api.init_decode_state(1, 64)
    out = []
    for a in range(0, prompt, chunk):
        lg, state = step(params, state, toks[None, a:a + chunk],
                         jnp.arange(a, a + chunk))
        out.append(lg[0])
    for p in range(prompt, len(toks)):
        lg, state = step(params, state, toks[None, p:p + 1], jnp.int32(p))
        out.append(lg[0])
    return np.concatenate([np.asarray(o, np.float32) for o in out])


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(3).integers(0, 256, 32).astype(np.int32)


@pytest.fixture(scope="module")
def reference_logits(tokens):
    cfg = smoke(held=4, first=4)
    return np.asarray(mla_moe_lm.logits(ref_config(cfg), SEED, tokens))


def test_prefill_then_latent_decode_matches_the_reference(tokens,
                                                         reference_logits):
    cfg = smoke(held=4, first=4)
    params = weights(cfg)
    got = served_logits(cfg, params, tokens)
    np.testing.assert_allclose(got, reference_logits, atol=ATOL, rtol=0)
    full, _ = build(cfg).forward(params, jnp.asarray(tokens)[None])
    np.testing.assert_allclose(np.asarray(full[0]), reference_logits,
                               atol=ATOL, rtol=0)


def test_bfloat16_falls_outside_the_tolerance(tokens, reference_logits):
    cfg = smoke(held=4, first=4, dtype="bfloat16")
    got = served_logits(cfg, weights(cfg), tokens)
    assert np.abs(got - reference_logits).max() > 10 * ATOL


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four chips' blocks of 2 of the 8 experts: their routed parts, with
    the shared expert (which every chip computes) counted once, are the
    uncut reference layer."""
    whole = smoke()
    params = weights(whole)
    moe = jax.tree.map(lambda v: v[0], params["layers"]["moe"])
    x = jax.random.normal(jax.random.key(1), (2, 5, whole.d_model))
    w = {f"moe/{k}": v for k, v in moe.items()}
    want = mla_moe_lm.experts(x.reshape(10, -1), w, ref_config(whole))
    shared = mla_moe_lm.swiglu(x.reshape(10, -1), moe["shared_wg"],
                               moe["shared_wi"], moe["shared_wo"])
    total = shared
    for chip in range(4):
        cfg = smoke(held=2, first=2 * chip)
        share = {k: (v[2 * chip:2 * chip + 2] if k in ("wi", "wg", "wo")
                     else v) for k, v in moe.items()}
        y, _, counts = moe_block(share, cfg, x)
        total = total + (y.reshape(10, -1) - shared)
        assert int(counts.sum()) == 10 * cfg.moe.top_k
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-5)


def test_the_call_shape_picks_the_expert_path():
    """Fewer routes than held experts: one guarded product per distinct
    expert hit, reading no other expert's weights; more: every held
    expert over every token, unguarded; from ``GROUPED_TOKENS`` tokens on,
    the routes sorted by expert through grouped products."""
    from repro.models import moe as moe_mod
    cfg = smoke()
    moe = jax.tree.map(lambda v: v[0], weights(cfg)["layers"]["moe"])

    def prims(n):
        x = jnp.zeros((1, n, cfg.d_model))
        jaxpr = jax.make_jaxpr(lambda p, x: moe_block(p, cfg, x))(moe, x)
        return str(jaxpr)

    one, many = prims(1), prims(8)
    grouped = prims(moe_mod.GROUPED_TOKENS)
    assert one.count("cond[") == cfg.moe.top_k
    assert "cond[" not in many and "ragged_dot" not in many
    assert "ragged_dot" in grouped and "cond[" not in grouped


@pytest.mark.parametrize("part", ["_every_held", "_per_expert", "_grouped"])
def test_each_expert_path_is_the_reference_layer(part):
    """Each way of computing the held part, on 2 of the 8 experts, against
    the reference layer of the same share."""
    from repro.models import moe as moe_mod
    whole, cfg = smoke(), smoke(held=2, first=4)
    p = jax.tree.map(lambda v: v[0], weights(whole)["layers"]["moe"])
    p = {k: (v[4:6] if k in ("wi", "wg", "wo") else v) for k, v in p.items()}
    x = jax.random.normal(jax.random.key(4), (5, cfg.d_model))
    _, gates, ids = moe_mod.route(p["router"], cfg, x)
    local = jnp.where((ids >= 4) & (ids < 6), ids - 4, 2)
    w = {k: p[k][None] for k in moe_mod.EXPERT_KEYS}
    got = getattr(moe_mod, part)(x, gates, local, w, 0, cfg)
    ref = mla_moe_lm.experts(x, {f"moe/{k}": v for k, v in p.items()},
                             ref_config(cfg)) - mla_moe_lm.swiglu(
        x, p["shared_wg"], p["shared_wi"], p["shared_wo"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)


def test_latent_decode_equals_the_decompressing_path():
    cfg = smoke()
    attn = jax.tree.map(lambda v: v[0], weights(cfg)["layers"]["attn"])
    m, H = cfg.mla, cfg.n_heads
    ks = jax.random.split(jax.random.key(2), 4)
    T, pos = 16, 9
    q_nope = jax.random.normal(ks[0], (1, 1, H, m.nope_head_dim))
    q_rope = jax.random.normal(ks[1], (1, 1, H, m.rope_head_dim))
    c_kv = jax.random.normal(ks[2], (1, T, m.kv_lora_rank))
    k_rope = jax.random.normal(ks[3], (1, T, m.rope_head_dim))
    q_pos = jnp.array([pos])
    latent = layers._mla_latent_decode(attn, cfg, q_nope, q_rope, c_kv,
                                       k_rope, q_pos)
    full = layers._mla_decompressed(attn, cfg, q_nope, q_rope, c_kv, k_rope,
                                    q_pos, jnp.arange(T))
    np.testing.assert_allclose(np.asarray(latent), np.asarray(full),
                               atol=1e-5)


def test_yarn_frequency_and_scale_by_hand():
    """DeepSeek-V2-Lite's YaRN at position 1000.  The correction dims are
    64 ln(4096 / (2 pi rot)) / (2 ln 10000): 10.47 for beta_fast 32,
    floored to 10, and 22.51 for beta_slow 1, ceiled to 23.  Frequency
    pairs 0-10 keep the original frequency, 23-31 take it over 40, and
    pair 15 lies 5/13 of the way along the ramp between.  The softmax
    scale is 192 ** -0.5 * (0.1 * 0.707 * ln 40 + 1) ** 2."""
    cfg = get_arch("deepseek-v2-lite-16b").full
    cos, sin = layers.rope_angles(jnp.array([1000]), 64, 10000.0, cfg.yarn)
    f = [10000.0 ** (-2 * i / 64) for i in range(32)]
    want = {3: f[3], 15: f[15] * (1 - 5 / 13) + f[15] / 40 * 5 / 13,
            25: f[25] / 40}
    for i, fi in want.items():
        assert float(cos[0, i]) == pytest.approx(math.cos(1000 * fi),
                                                 abs=1e-4)
        assert float(sin[0, i]) == pytest.approx(math.sin(1000 * fi),
                                                 abs=1e-4)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert layers.mla_softmax_scale(cfg) == pytest.approx(
        192 ** -0.5 * m * m, rel=1e-12)
    ref = ref_config(cfg)
    np.testing.assert_allclose(mla_moe_lm.yarn_inv_freq(ref),
                               layers.rope_freqs(64, 10000.0, cfg.yarn),
                               rtol=1e-6)
    assert mla_moe_lm.softmax_scale(ref) == pytest.approx(
        layers.mla_softmax_scale(cfg), rel=1e-12)


def test_jit_backend_counts_are_the_reference_routing():
    """The routes the serving backend reads out of each call, summed over
    a request, are the reference's routing of the same tokens; the held
    experts' regions carry them, and the parent region their sum."""
    from repro.core import FLOPS
    from repro.scenarios.traffic import (TrafficConfig, generate_traffic,
                                         prompt_tokens)
    from repro.serve import ServeConfig, ServeEngine
    from repro.serve.runtime import JitBackend

    cfg = smoke(held=4, first=2)
    api = build(cfg)
    traffic = generate_traffic(TrafficConfig(
        n_requests=1, arrival_rate=10.0, length_buckets=(16,),
        length_mix=(1.0,), gen_len=6, vocab=cfg.vocab), seed=0)
    backend = JitBackend(cfg, api, weights(cfg), lanes=1, max_len=32,
                         prefill_chunk=8, seed=0)
    engine = ServeEngine(ServeConfig(lanes=1, max_len=32, prefill_chunk=8),
                         traffic, backend)
    tr = engine.run()
    seq = np.concatenate([prompt_tokens(traffic[0], cfg.vocab, 0)[0],
                          backend.outputs[0]])
    want = mla_moe_lm.routed_counts(ref_config(cfg), SEED, seq)
    held = want[:, 2:6].sum(0)
    fl = tr.metric(FLOPS).sum(axis=(0, 1, 2))
    per_route = 6.0 * cfg.d_model * cfg.moe.d_ff
    for e in range(2, 6):
        rid = backend.tree.by_path(f"serve/moe/expert_{e}").region_id
        assert fl[tr.col(rid)] == pytest.approx(held[e - 2] * per_route)
    rid = backend.tree.by_path("serve/moe").region_id
    assert fl[tr.col(rid)] == pytest.approx(held.sum() * per_route)
    assert [r.name for r in backend.tree.by_path("serve/moe").children] \
        == [f"expert_{e}" for e in range(2, 6)]


def test_dispatch_spans_carry_the_routes(tmp_path):
    from repro.core import spans
    from repro.scenarios.traffic import TrafficConfig, generate_traffic
    from repro.serve import ServeConfig, ServeEngine
    from repro.serve.runtime import JitBackend

    cfg = smoke(held=4)
    api = build(cfg)
    traffic = generate_traffic(TrafficConfig(
        n_requests=2, arrival_rate=10.0, length_buckets=(8,),
        length_mix=(1.0,), gen_len=3, vocab=cfg.vocab), seed=0)
    backend = JitBackend(cfg, api, weights(cfg), lanes=2, max_len=16,
                         prefill_chunk=8, seed=0)
    engine = ServeEngine(ServeConfig(lanes=2, max_len=16, prefill_chunk=8),
                         traffic, backend)
    backend.warmup()
    engine.step()
    spans.take()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        while engine.step():
            pass
    calls = [s for s in spans.take()["spans"] if s.name == "serve.dispatch"]
    layers_moe = cfg.n_layers - cfg.moe.first_dense
    model = [s for s in calls if s.attrs["kind"] in ("prefill", "decode")]
    assert model and all(s.attrs["tokens"] >= 1 for s in model)
    for s in model:
        a = s.attrs
        routes = a["tokens"] * cfg.moe.top_k * layers_moe
        assert 0 <= a["experts_hit"] <= a["routes_held"] <= routes
        assert a["experts_hit"] <= layers_moe * cfg.moe.n_held
    assert any(s.attrs["routes_held"] > 0 for s in model)
    assert all("routes_held" not in s.attrs for s in calls
               if s.attrs["kind"] == "sample")
