"""Roofline share of the decode calls of the latent-attention expert
model: the least time each call's work (``bench.costs_mla_moe``: every
weight outside the routed experts once, the held experts one token is
expected to reach, the latent cache prefix) needs at the chip's peaks,
over the device time inside the calls' ``bench:call:decode`` spans,
summed over the decode calls the trace holds."""
from bench import costs, costs_mla_moe, xplane


def read(rec):
    ev = rec.get("profile")
    if not ev:
        return None
    calls = []
    for label, _, device_s in xplane.span_device_time(ev, "call"):
        kind, _, pos = label.partition(":")
        if kind == "decode":
            calls.append((int(pos), device_s))
    dev = sum(s for _, s in calls)
    if dev <= 0:
        return None
    pk, cfg = costs.peaks(rec["device_kind"]), rec["config"]
    need = sum(costs.roofline_s(costs_mla_moe.decode_flops(cfg, p),
                                costs_mla_moe.decode_bytes(cfg, p), pk)
               for p, _ in calls)
    return 100.0 * need / dev
