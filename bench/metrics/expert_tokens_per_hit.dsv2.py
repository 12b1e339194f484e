"""Token-routes served per expert weight read at decode: the routes that
landed on held experts over the held experts that had any, summed over
the expert layers of every decode call in the traced window (the
``routes_held`` and ``experts_hit`` attributes of the program's
``serve.dispatch`` spans).  A program whose spans carry no such counts
gives nothing to read."""
from bench import program


def read(rec):
    prog = program.spans(rec)
    if prog is None:
        return None
    routes = hit = 0
    for s in program.named(prog, "serve.dispatch"):
        if s.attrs.get("kind") == "decode" and "experts_hit" in s.attrs:
            routes += s.attrs["routes_held"]
            hit += s.attrs["experts_hit"]
    return routes / hit if hit else None
