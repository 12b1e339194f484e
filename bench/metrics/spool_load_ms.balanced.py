"""Host milliseconds per window loading spool segments: file read and
npz decode (the program's ``spool.load`` spans over its
``online.consume`` spans)."""
from bench import program


def read(rec):
    return program.per_window_ms(rec, "spool.load")
