"""``idle_engine_share.serve`` read in the latent-attention expert cell:
the share of the traced window in which the device is idle under the
engine's host work."""
from bench import harness

read = harness.metric_reader("idle_engine_share.serve")
