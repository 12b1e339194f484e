"""Operation and byte counts from shapes, checked by hand at small sizes,
and the table of peaks."""
import pytest

from bench import costs

TINY = {"num_hidden_layers": 2, "hidden_size": 8, "num_attention_heads": 2,
        "num_key_value_heads": 1, "head_dim": 4, "intermediate_size": 16,
        "vocab_size": 10}


def test_v5e_peaks():
    pk = costs.peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9
    assert pk["hbm_bytes"] == 16e9


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        costs.peaks("TPU v4")
    with pytest.raises(KeyError):
        costs.peaks("cpu")


def test_d2_rows():
    # 3 rows over 4 points in R^2: 3 * (2*4*2 + 3*4) operations; the points
    # (4*2 floats) and norms (4) read once, 3 rows of 4 written.
    assert costs.d2_rows(4, 2, 3) == {"flops": 3 * (16 + 12),
                                      "bytes": 4 * (8 + 4) + 4 * 4 * 3}


def test_dense_token_counts():
    # one layer: q 8*2*4 + k,v 2*8*1*4 + o 2*4*8 + mlp 3*8*16 = 576
    assert costs.layer_params(TINY) == 576
    # position 5 attends 6 keys: 4 * L * H * dh * 6 = 4*2*2*4*6
    assert costs.token_flops(TINY, 5) == 2 * 2 * 576 + 2 * 8 * 10 + 384
    assert costs.span_flops(TINY, 3, 4) == pytest.approx(
        sum(costs.token_flops(TINY, p) for p in range(3, 7)))


def test_decode_bytes():
    # weights: 2 layers * (576 + 2*8 norms) + final norm 8 + head 80 +
    # one embedding row 8; keys and values 2*2*1*4 = 16 per position,
    # 7 earlier positions read and 1 written; bf16.
    assert costs.decode_bytes(TINY, 7) == 2 * (2 * 592 + 8 + 80 + 8
                                               + 16 * 7 + 16)


def test_full_width_weights_are_7_92_gb():
    from bench import harness
    cfg = harness.load_json(f"{harness.BENCH_DIR}/configs/h2o-danube3-4b.json")
    L = cfg["num_hidden_layers"]
    total = L * (costs.layer_params(cfg) + 2 * cfg["hidden_size"]) \
        + cfg["hidden_size"] * (2 * cfg["vocab_size"] + 1)
    assert round(2 * total / 1e9, 2) == 7.92
