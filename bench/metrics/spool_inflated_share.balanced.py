"""Share of the member bytes that the window's segment loads had to
inflate, in percent: the ``inflated_bytes`` over the ``raw_bytes`` that
``RegionTrace.load`` counts into the program's ``spool.load`` spans.  A
program whose loads count neither gives nothing to read."""
from bench import program


def read(rec):
    prog = program.spans(rec)
    if prog is None:
        return None
    loads = [s.attrs for s in program.named(prog, "spool.load")
             if "raw_bytes" in s.attrs]
    raw = sum(a["raw_bytes"] for a in loads)
    if not raw:
        return None
    return 100 * sum(a["inflated_bytes"] for a in loads) / raw
