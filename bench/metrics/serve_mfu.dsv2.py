"""The whole serving window's share of the chip's bf16 peak for the
latent-attention expert model: the operations of every prefill and decode
token served in it (``bench.costs_mla_moe``, from the configuration's
shapes), over the window's seconds."""
from bench import costs


def read(rec):
    flops = rec.get("window_flops")
    if not flops or not rec.get("window_s"):
        return None
    pk = costs.peaks(rec["device_kind"])
    return 100.0 * flops / rec["window_s"] / pk["bf16_flops_per_s"]
