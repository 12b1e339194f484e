"""``device_idle.serve`` read in the latent-attention expert cell: the
share of the traced window in which no operation ran on the device."""
from bench import harness

read = harness.metric_reader("device_idle.serve")
