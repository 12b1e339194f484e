"""Host milliseconds per window inside the rough-set root-cause pass
(``DecisionTable.reducts`` and ``object_reducts``)."""


def read(rec):
    if not rec.get("windows") or "rootcause" not in rec["spans"]:
        return None
    return 1e3 * rec["spans"]["rootcause"] / rec["windows"]
