"""95th-percentile time (ms) from each clip's due time to its first reply:
admission wait plus one tick."""
from bench.readers import latency_ms


def read(run):
    return latency_ms(run, "first_reply", 95)
