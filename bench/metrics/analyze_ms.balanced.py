"""Host milliseconds per window inside the analyzer: the trace reduction
and ``AutoAnalyzer.analyze`` (``analyze_trace``)."""


def read(rec):
    if not rec.get("windows") or "analyze" not in rec["spans"]:
        return None
    return 1e3 * rec["spans"]["analyze"] / rec["windows"]
