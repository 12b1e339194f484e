"""Roofline share of the squared-distance row work.  The work is what the
exact float64 lane fetches for the same windows, m·n per seed row
(``bench.costs.d2_rows``); the time is the device time inside the
clustering spans, so the share reads the same work whatever computes the
rows."""
from bench import costs, xplane


def read(rec):
    work, ev = rec.get("d2_work"), rec.get("profile")
    if not work or not work["flops"] or not ev:
        return None
    device_s = sum(d for _, _, d in xplane.span_device_time(ev, "clustering"))
    if device_s <= 0:
        return None
    pk = costs.peaks(rec["device_kind"])
    return 100.0 * costs.roofline_s(work["flops"], work["bytes"], pk) \
        / device_s
