"""Share of the traced window in which no operation ran on the device:
one less the union of the device's op intervals over the window."""


def read(rec):
    if not rec.get("traced_s") or "busy_s" not in rec:
        return None
    return 100.0 * (1.0 - rec["busy_s"] / rec["traced_s"])
