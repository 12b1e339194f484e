"""Host milliseconds per engine step outside the waits for the device:
each ``serve.step`` span less the ``serve.wait`` spans inside it, averaged
over the steps the traced window holds."""
from bench import program


def read(rec):
    prog = program.spans(rec)
    if prog is None:
        return None
    steps = program.named(prog, "serve.step")
    if not steps:
        return None
    by_id = {s.id: s for s in prog["spans"]}
    waited = {s.id: 0 for s in steps}
    for w in program.named(prog, "serve.wait"):
        step = program.ancestor(w, "serve.step", by_id)
        if step is not None:
            waited[step.id] += w.t1_ns - w.t0_ns
    host = sum(s.t1_ns - s.t0_ns - waited[s.id] for s in steps)
    return host / 1e6 / len(steps)
