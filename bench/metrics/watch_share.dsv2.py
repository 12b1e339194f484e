"""``watch_share.serve`` read in the latent-attention expert cell: the
share of the serving window's host time spent in the watcher's poll of
the engine's spool."""
from bench import harness

read = harness.metric_reader("watch_share.serve")
