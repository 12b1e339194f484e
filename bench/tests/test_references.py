"""The benchmark's copies and references against the program at sizes the
CPU holds: the ST behaviour table still gives the paper's outcome, the
analyzer reference agrees with the exact lane, the traffic copy draws what
the program's generator draws, and the float32 model reference computes
the program's model."""
import numpy as np
import pytest

from bench import st_job, traffic
from bench.reference import analyzer as ref
from bench.reference import dense_lm
from bench.tests import small


def _windows(tr_name, m, n=2, seed=3):
    _, config, tr = small.st_cell(tr_name, m)
    rng = np.random.default_rng(seed)
    return config, [st_job.window_data(config, tr, rng, 8) for _ in range(n)]


def _program_doc(config, data):
    from repro.core import AutoAnalyzer, tree_from_schema
    from repro.core.trace import RegionTrace
    schema = st_job.schema(config)
    tr = RegionTrace(region_ids=st_job.region_ids(config),
                     n_processes=config["n_processes"], n_steps=8,
                     schema=schema, data=data)
    return AutoAnalyzer(tree_from_schema(schema)).analyze(tr.reduce()).verdict


def _reference(config, data, dtype=np.float64):
    rm = ref.reduce(data, {}, dtype)
    return ref.analyze(ref.Tree(st_job.schema(config)),
                       st_job.region_ids(config), rm, dtype)


def test_st_copy_gives_the_papers_outcome_at_8_ranks():
    config, wins = _windows("imbalanced", 8)
    v = _program_doc(config, wins[0])
    assert v.dissimilarity_paths == ("ST/cr14/cr11/cr21",)
    assert v.disparity_paths == ("ST/cr14/cr11/cr21", "ST/cr8/cr19")
    assert v.dissimilarity_cause_attributes == frozenset({"flops"})
    assert dict(v.per_path_causes)["ST/cr8/cr19"] == ("host_bytes",)
    assert dict(v.per_path_causes)["ST/cr14/cr11/cr21"] == ("hbm_intensity",)


@pytest.mark.parametrize("tr_name,m", [("imbalanced", 8), ("imbalanced", 64),
                                       ("balanced", 64)])
def test_analyzer_reference_agrees_with_the_exact_lane(tr_name, m):
    config, wins = _windows(tr_name, m)
    for data in wins:
        doc, _ = _reference(config, data)
        assert doc == _program_doc(config, data).doc()
        assert doc["dissimilar"] is (tr_name == "imbalanced")


def test_traffic_copy_draws_what_the_program_draws():
    from repro.scenarios import traffic as prog
    t = {"n_requests": 40, "arrival_rate": 1.5, "burstiness": 0.2,
         "length_buckets": [8, 16, 32], "length_mix": [0.5, 0.3, 0.2],
         "gen_len": 6, "gen_jitter": 2, "hot_fraction": 0.1, "sessions": 3}
    mine = traffic.generate(t, 256, 2 ** 31 + 77)
    theirs = prog.generate_traffic(prog.TrafficConfig(
        n_requests=40, arrival_rate=1.5, burstiness=0.2,
        length_buckets=(8, 16, 32), length_mix=(0.5, 0.3, 0.2), gen_len=6,
        gen_jitter=2, hot_fraction=0.1, sessions=3, vocab=256),
        seed=2 ** 31 + 77)
    assert [vars(r) for r in mine] == [vars(r) for r in theirs]
    for a, b in zip(mine, theirs):
        assert np.array_equal(traffic.prompt_tokens(a, 256, 5),
                              prog.prompt_tokens(b, 256, 5))


def test_quantile_deal_serves_every_seed_the_same_lengths():
    t = {"n_requests": 48, "arrival_rate": None, "prompt_median": 1020,
         "prompt_sigma": 0.6, "output_median": 129, "output_sigma": 0.8,
         "quantile_block": 16, "prompt_multiple": 256, "max_positions": 4096}
    blocks = []
    for seed in (1, 2 ** 31 + 3):
        reqs = sorted(traffic.generate(t, 100, seed), key=lambda r: r.rid)
        assert all(r.arrival_step == 0 for r in reqs)
        assert all(r.prompt_len % 256 == 0 and r.prompt_len >= 256
                   for r in reqs)
        assert all(r.prompt_len + r.gen_len < 4096 for r in reqs)
        for b in range(3):
            blk = reqs[16 * b:16 * b + 16]
            blocks.append((sorted(r.prompt_len for r in blk),
                           sorted(r.gen_len for r in blk)))
        prompts = sorted(r.raw_len for r in reqs[:16])
        gens = sorted(r.gen_len for r in reqs[:16])
        # the medians of the published distribution sit mid-block
        assert prompts[7] < 1020 < prompts[8]
        assert gens[7] < 129 < gens[8]
    assert all(b == blocks[0] for b in blocks)
    a = [r.gen_len for r in traffic.generate(t, 100, 1)]
    b = [r.gen_len for r in traffic.generate(t, 100, 2)]
    assert a != b and sorted(a) == sorted(b)


def test_one_layer_redrawn_is_the_stacked_layer():
    _, c, _ = small.chat_cell()
    params = dense_lm.init_params(c, 2 ** 31 + 1)
    w = dense_lm.layer_weights(c, 2 ** 31 + 1, 1)
    for key in ("attn/wq", "mlp/wo", "ln2"):
        node = params["layers"]
        for k in key.split("/"):
            node = node[k]
        assert np.array_equal(np.asarray(node[1], np.float32),
                              np.asarray(w[key]))


def test_model_reference_computes_the_programs_model():
    """The program's float32 forward pass against the float32 reference
    on one sequence: the same gap of every next token below the top
    logit, to rounding."""
    import jax
    from bench.drivers import serve_watched
    from repro.models import build
    _, c, _ = small.chat_cell("float32")
    seed = 11
    api = build(serve_watched.model_config(c))
    params = jax.tree.map(lambda x: x.astype(np.float32),
                          dense_lm.init_params(c, seed))
    seq = np.random.default_rng(0).integers(0, 256, 40).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(api.forward(params, seq[None])[0][0])
    nxt = np.roll(seq, -1)
    want = logits.max(-1) - logits[np.arange(40), nxt]
    gap, = dense_lm.forward_gaps(c, seed, [seq], [range(39)], 48)["none"]
    np.testing.assert_allclose(gap[:39], want[:39], atol=1e-4)
    assert (want[:39] > 0).sum() > 30
