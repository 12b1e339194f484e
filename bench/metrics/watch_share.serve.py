"""Share of the serving window's host time spent in the watcher's poll of
the engine's spool (``OnlineAnalyzer.pending_bounds``/``consume``)."""


def read(rec):
    if not rec.get("window_s") or not rec.get("watch_s"):
        return None
    return 100.0 * rec["watch_s"] / rec["window_s"]
