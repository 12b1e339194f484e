"""Host milliseconds per window inside ``SpooledTrace.window`` (segment
read and reassembly)."""


def read(rec):
    if not rec.get("windows") or "spool_read" not in rec["spans"]:
        return None
    return 1e3 * rec["spans"]["spool_read"] / rec["windows"]
