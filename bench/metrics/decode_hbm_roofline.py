"""Roofline share of the decode calls: the least time each call's bytes
(every weight read once, the live key/value prefix, from the shapes) and
operations need at the chip's peaks, over the device time inside the
calls, summed over the decode calls the trace holds."""
from bench import costs, xplane


def read(rec):
    ev = rec.get("profile")
    if not ev:
        return None
    pk, cfg = costs.peaks(rec["device_kind"]), rec["config"]
    need = dev = 0.0
    for label, _, device_s in xplane.span_device_time(ev, "call"):
        kind, _, pos = label.partition(":")
        if kind == "decode":
            p = int(pos)
            need += costs.roofline_s(costs.token_flops(cfg, p),
                                     costs.decode_bytes(cfg, p), pk)
            dev += device_s
    return 100.0 * need / dev if dev > 0 else None
