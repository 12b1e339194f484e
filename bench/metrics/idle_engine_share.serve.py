"""Share of the traced window in which the device is idle while the
innermost open program span is the engine's host work: a ``serve.*``
span other than ``serve.wait``, or a ``spool.*`` span inside
``serve.step``.

The program's spans run on ``perf_counter_ns`` and the device ops on the
trace's clock.  They are put on one clock by the offset that puts the
device's busy time inside the ``serve.dispatch`` and ``serve.wait`` spans,
every device op of the engine being launched and waited for there; the
reading is left out where, so aligned, under 95% of the busy time lies
inside them."""
from collections import defaultdict

from bench import program, xplane


def engine_host(s, by_id):
    if s.name.startswith("serve."):
        return s.name != "serve.wait"
    return (s.name.startswith("spool.")
            and program.ancestor(s, "serve.step", by_id) is not None)


def read(rec):
    prog, ev = program.spans(rec), rec.get("profile")
    if prog is None or not ev or not ev["device"]:
        return None
    busy = xplane.busy_by_plane(ev)[sorted(ev["device"])[0]]
    origin = prog["origin_ns"]
    calls = [(s.t0_ns - origin, s.t1_ns - origin) for s in prog["spans"]
             if s.name in ("serve.dispatch", "serve.wait")]
    if not calls:
        return None
    d, share = program.fit_offset(busy, calls)
    if share < program.MIN_COVER:
        return None
    cover = xplane.Cover([(a - d + origin, b - d + origin) for a, b in busy])
    by_id = {s.id: s for s in prog["spans"]}
    children = defaultdict(list)
    for s in prog["spans"]:
        children[s.parent].append(s)
    idle = 0.0
    for s in prog["spans"]:
        if engine_host(s, by_id):
            for a, b in program.self_time(s, children[s.id]):
                idle += (b - a) - cover(a, b)
    return 100.0 * idle / 1e9 / rec["traced_s"]
