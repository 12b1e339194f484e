"""Host milliseconds per window inside clustering: Algorithm 2
(``find_dissimilarity_bottlenecks``), ``optics_cluster`` and
``kmeans_severity``."""


def read(rec):
    if not rec.get("windows") or "clustering" not in rec["spans"]:
        return None
    return 1e3 * rec["spans"]["clustering"] / rec["windows"]
