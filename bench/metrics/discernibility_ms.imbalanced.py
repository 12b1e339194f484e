"""Host milliseconds per window in the rough-set discernibility pair
loop and its absorption (the program's ``roughset.discernibility`` spans
over its ``online.consume`` spans)."""
from bench import program


def read(rec):
    return program.per_window_ms(rec, "roughset.discernibility")
