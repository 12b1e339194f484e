"""The whole serving window's share of the chip's peak: the operations of
every prefill and decode token served in it (``bench.costs``, from the
configuration's shapes), over the window's seconds and the bf16 peak."""
from bench import costs


def read(rec):
    flops = rec.get("window_flops")
    if not flops or not rec.get("window_s"):
        return None
    pk = costs.peaks(rec["device_kind"])
    return 100.0 * flops / rec["window_s"] / pk["bf16_flops_per_s"]
