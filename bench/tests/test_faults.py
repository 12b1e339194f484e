"""A run with the timed path broken underneath comes out not correct: for
each fault a cell can have, planted where the program produces its answer."""
import pytest

from bench.tests import small


@pytest.mark.parametrize("fault", ["answer", "half_batch", "stale"])
def test_analyzer_fault_is_caught(fault):
    doc, _ = small.run(*small.st_cell("imbalanced"), faults=[fault])
    assert doc["correct"] is False


@pytest.mark.parametrize("fault", ["answer", "stale"])
def test_serving_fault_is_caught(fault):
    doc, _ = small.run(*small.chat_cell(), faults=[fault])
    assert doc["correct"] is False
    assert doc["checks"]["logit_gap"]["value"] > \
        doc["checks"]["logit_gap"]["limit"]
