"""Host milliseconds per fleet tick: the tick's wall time less the device busy
time inside it, averaged over the traced ticks."""
from bench.readers import host_ms_per_tick


def read(run):
    return host_ms_per_tick(run)
