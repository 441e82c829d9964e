"""95th-percentile latency (ms) from each clip's due time to its final readout
on the host."""
from bench.readers import latency_ms


def read(run):
    return latency_ms(run, "done", 95)
