"""Host milliseconds per window that clustering waits on device-to-host
pulls: the lockstep round sync, seed rows and the k-means result (the
program's ``clustering.device_wait`` spans over its ``online.consume``
spans)."""
from bench import program


def read(rec):
    return program.per_window_ms(rec, "clustering.device_wait")
