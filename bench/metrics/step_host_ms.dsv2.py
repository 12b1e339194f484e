"""``step_host_ms.serve`` read in the latent-attention expert cell: host
milliseconds per engine step outside the waits for the device."""
from bench import harness

read = harness.metric_reader("step_host_ms.serve")
