"""The controls come out not correct: the references put in the program's
place in the next precision down, at sizes the CPU holds, judged by the
rule that decides ``correct``.  (On the chip, at the cells' own sizes:
``bench/control.py``.)"""
import numpy as np

from bench import harness
from bench.drivers import analyzer_backlog, serve_watched
from bench.reference import analyzer as ref
from bench.reference import dense_lm
from bench.tests import small
from bench.tests.test_references import _reference, _windows


def test_float32_analyzer_reference_fails_the_value_gap():
    for tr_name in ("imbalanced", "balanced"):
        config, wins = _windows(tr_name, 64)
        for data in wins:
            doc, nums = _reference(config, data)
            doc32, nums32 = _reference(config, data, np.float32)
            gap = ref.value_gap(nums32, nums)
            assert gap > 3 * analyzer_backlog.LIMITS["value_gap"], gap
            checks = {k: {"value": 0.0, "limit": v}
                      for k, v in analyzer_backlog.LIMITS.items()}
            assert harness.judge(checks)
            assert not harness.judge(harness.with_control(
                checks, {"verdict_mismatches": float(doc32 != doc),
                         "value_gap": gap}))


def test_lower_precision_model_reads_a_wider_gap():
    """The model's control at a size the CPU holds, on 1020 positions of
    four sequences: the reference in fp8, read at the token it puts first,
    lies further below the float32 reference's best than the limit, so it
    comes out not correct; int8 flips tokens too, though at this depth
    and width by less than the limit (at the cell's own size on the chip
    it reads above it: PERF.md)."""
    _, c, _ = small.chat_cell()
    c.update(num_hidden_layers=4, hidden_size=256, intermediate_size=768,
             vocab_size=4096, num_attention_heads=8, head_dim=32)
    checks = {k: {"value": 0.0, "limit": v}
              for k, v in serve_watched.LIMITS.items()}
    for seed in (5, 2 ** 31 + 7):
        rng = np.random.default_rng(seed)
        seqs = [rng.integers(0, 4096, 256).astype(np.int32)
                for _ in range(4)]
        gaps = dense_lm.forward_gaps(c, seed, seqs, [range(255)] * 4, 256,
                                     ("int8", "fp8"))
        read = {q: max(float(g.max()) for g in gaps[q])
                for q in ("int8", "fp8")}
        assert read["fp8"] > 2 * serve_watched.LIMITS["logit_gap"], read
        assert not harness.judge(harness.with_control(
            checks, {"logit_gap": read["fp8"]}))
        assert read["int8"] > 0, read


def test_controls_of_a_served_run_are_judged_by_the_rule():
    """A served run at a size the CPU holds returns each control's
    readings; judged by the rule that decides ``correct`` with them in
    the program's place, the float32 analyzer comes out not correct, and
    the program correct."""
    cell, c, tr = small.chat_cell()
    ctx = harness.Context(cell, c, tr, 5, 1.0, False, 0.0)
    ctx.control = True
    try:
        res = serve_watched.run(ctx)
    finally:
        ctx.close()
    assert harness.judge(res["checks"]), res["checks"]
    verdicts = harness.control_verdicts(res)
    assert set(verdicts) == {"model.int8", "model.fp8", "analyzer.float32"}
    assert verdicts["analyzer.float32"]["correct"] is False, verdicts
    for q in ("int8", "fp8"):
        assert res["controls"][f"model.{q}"]["logit_gap"] >= 0
