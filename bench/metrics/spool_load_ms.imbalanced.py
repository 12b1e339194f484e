"""``spool_load_ms.balanced`` read in the imbalanced cell: host
milliseconds per window loading spool segments (the program's
``spool.load`` spans over its ``online.consume`` spans)."""
from bench import harness

read = harness.metric_reader("spool_load_ms.balanced")
