"""The latent-attention expert cell cut to a size the CPU holds: its
reference computes the program's model, its costs are the hand counts,
faults planted in its timed path come out not correct, and the program
before the configuration's fields existed fails it at once."""
import numpy as np
import pytest

from bench import costs_mla_moe, harness
from bench.drivers import serve_watched_moe
from bench.reference import mla_moe_lm
from bench.tests import small


def moe_cell(dtype: str = "bfloat16", held: int = 4, first: int = 0):
    config = harness.load_json(
        f"{harness.BENCH_DIR}/configs/deepseek-v2-lite-ep4.json")
    config.update(num_hidden_layers=3, hidden_size=64, num_attention_heads=4,
                  num_key_value_heads=4, kv_lora_rank=16, qk_nope_head_dim=16,
                  qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
                  moe_intermediate_size=32, n_routed_experts_published=8,
                  n_routed_experts=held, first_held_expert=first,
                  num_experts_per_tok=3, n_shared_experts=1, vocab_size=256,
                  torch_dtype=dtype)
    config["rope_scaling"] = dict(config["rope_scaling"], factor=4,
                                  original_max_position_embeddings=64)
    tr = harness.load_json(f"{harness.BENCH_DIR}/traffic/chat16.json")
    tr.update(prompt_median=48, prompt_multiple=16, output_median=8,
              max_positions=256, prefill_chunk=16, lanes=2, n_requests=400,
              warm_steps=8, check_requests=2)
    return {"name": "deepseek-v2-lite-ep4.chat16", "chips": 1}, config, tr


def test_a_served_run_is_correct_and_reads_its_metrics(monkeypatch):
    # The CPU is in no table of peaks: the readers are given the chip's
    # so that the run completes (its shares mean nothing here).
    from bench import costs
    chip = costs.peaks("TPU v5 lite")
    monkeypatch.setattr(costs, "peaks", lambda kind: chip)
    cell, c, tr = moe_cell()
    tr["trace_seconds"] = 0.3      # the profiler stops inside the window
    doc, err = small.run(cell, c, tr, trace=True)
    assert doc["correct"] is True, err
    assert doc["checks"]["logit_gap"]["value"] < 0.05
    m = doc["metrics"]
    assert {"serve_mfu.dsv2", "expert_tokens_per_hit.dsv2",
            "device_idle.dsv2", "watch_share.dsv2",
            "step_host_ms.dsv2"} <= set(m), m
    assert m["expert_tokens_per_hit.dsv2"]["value"] >= 1.0
    assert m["serve_mfu.dsv2"]["value"] > 0
    assert 0 < m["watch_share.dsv2"]["value"] < 100


def test_one_layer_redrawn_is_the_stacked_layer():
    _, c, _ = moe_cell()
    params = mla_moe_lm.init_params(c, 2 ** 31 + 1)
    for layer, stack, i in ((0, "dense", 0), (2, "layers", 1)):
        w = mla_moe_lm.layer_weights(c, 2 ** 31 + 1, layer)
        for key in ("attn/wkv_b", "attn/kv_norm", "ln2") + (
                ("mlp/wo",) if stack == "dense" else ("moe/wi", "moe/router")):
            node = params[stack]
            for k in key.split("/"):
                node = node[k]
            assert np.array_equal(np.asarray(node[i], np.float32),
                                  np.asarray(w[key])), key


def test_model_reference_computes_the_programs_model():
    """The program's float32 forward pass against the float32 reference
    on one sequence: the same gap of every next token below the top
    logit, to rounding."""
    import jax
    from repro.models import build
    _, c, _ = moe_cell("float32")
    seed = 11
    api = build(serve_watched_moe.model_config(c))
    params = jax.tree.map(lambda x: x.astype(np.float32),
                          mla_moe_lm.init_params(c, seed))
    seq = np.random.default_rng(0).integers(0, 256, 40).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(api.forward(params, seq[None])[0][0])
    nxt = np.roll(seq, -1)
    want = logits.max(-1) - logits[np.arange(40), nxt]
    gap, = mla_moe_lm.forward_gaps(c, seed, [seq], [range(39)], 48)["none"]
    np.testing.assert_allclose(gap[:39], want[:39], atol=1e-4)
    assert (want[:39] > 0).sum() > 30


def test_costs_by_hand():
    _, c, _ = moe_cell()
    # d 64, H 4, r 16, dn 16, dr 8, dv 16, ff 96, fe 32, E 8, held 4, k 3,
    # one shared expert, vocabulary 256, 3 layers of which 1 dense.
    attn = 64 * 4 * 24 + 64 * 24 + 16 * 4 * 32 + 4 * 16 * 64
    assert costs_mla_moe.attn_params(c) == attn == 13824
    assert costs_mla_moe.expert_params(c) == 3 * 64 * 32
    assert costs_mla_moe.shared_params(c) == attn + 64 * 8 + 3 * 64 * 32
    assert costs_mla_moe.dense_params(c) == attn + 3 * 64 * 96
    # one token reaches 4 * (1 - (5/8)) = 1.5 held experts; two, 2.4375
    assert costs_mla_moe.experts_hit(c, 1) == pytest.approx(1.5)
    assert costs_mla_moe.experts_hit(c, 2) == pytest.approx(
        4 * (1 - (5 / 8) ** 2))
    matmuls = 2 * (32256 + 2 * (20480 + 1.5 * 6144) + 64 * 256)
    # prompt token at 5: 6 keys at full rank, 2 * 3 * 4 * (24 + 16) each
    assert costs_mla_moe.prefill_flops(c, 5) == pytest.approx(
        matmuls + 6 * 960)
    # decoded token at 5: 6 cached positions in latent space,
    # 2 * 3 * 4 * (16 + 8 + 16) each
    assert costs_mla_moe.decode_flops(c, 5) == pytest.approx(
        matmuls + 6 * 960)
    assert costs_mla_moe.span_flops(c, 3, 4) == pytest.approx(
        sum(costs_mla_moe.prefill_flops(c, p) for p in range(3, 7)))
    # bytes at position 7, bf16: the dense layer, 2 expert layers of
    # shared weights and 1.5 experts, one embedding row, the head, the
    # norms (3 layers of 2 * 64 + 16, and the final 64), and 7 earlier
    # positions of 3 * (16 + 8) cache values read and one written
    weights = 32256 + 2 * (20480 + 1.5 * 6144) + 64 + 64 * 256 \
        + 3 * 144 + 64
    assert costs_mla_moe.decode_bytes(c, 7) == pytest.approx(
        2 * (weights + 72 * 8))


def test_full_width_reckoning():
    """The configuration's own numbers: 4.91 B held parameters, 9.82 GB in
    bf16, and about 2.9 GB read by a decode call at position 2000."""
    c = harness.load_json(
        f"{harness.BENCH_DIR}/configs/deepseek-v2-lite-ep4.json")
    L, Ld, held = c["num_hidden_layers"], c["first_k_dense_replace"], \
        c["n_routed_experts"]
    total = (2 * c["vocab_size"] * c["hidden_size"]
             + Ld * costs_mla_moe.dense_params(c)
             + (L - Ld) * (costs_mla_moe.shared_params(c)
                           + held * costs_mla_moe.expert_params(c)))
    assert round(total / 1e9, 2) == 4.91
    assert round(2 * total / 1e9, 2) == 9.82
    assert round(costs_mla_moe.decode_bytes(c, 2000) / 1e9, 1) == 2.9


@pytest.mark.parametrize("fault", ["answer", "stale", "skip_experts"])
def test_serving_fault_is_caught(fault):
    doc, _ = small.run(*moe_cell(), faults=[fault])
    assert doc["correct"] is False
    gap = doc["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


def test_controls_of_a_served_run_are_judged_by_the_rule():
    cell, c, tr = moe_cell()
    ctx = harness.Context(cell, c, tr, 5, 1.0, False, 0.0)
    ctx.control = True
    try:
        res = serve_watched_moe.run(ctx)
    finally:
        ctx.close()
    assert harness.judge(res["checks"]), res["checks"]
    verdicts = harness.control_verdicts(res)
    assert set(verdicts) == {"model.int8", "model.fp8", "analyzer.float32"}
    assert verdicts["analyzer.float32"]["correct"] is False, verdicts
    for q in ("int8", "fp8"):
        assert res["controls"][f"model.{q}"]["logit_gap"] >= 0


def test_a_program_without_the_fields_fails_at_construction(monkeypatch):
    """The configuration's fields are passed to the program's model: one
    that lacks them (here, YaRN) fails before any weight is drawn."""
    from repro.configs import base
    monkeypatch.delattr(base, "YarnConfig")
    _, c, _ = moe_cell()
    with pytest.raises(ImportError):
        serve_watched_moe.model_config(c)
