"""Share (%) of the traced window in which no operation ran on the device,
averaged over the chips."""
from bench.readers import device_idle_pct


def read(run):
    return device_idle_pct(run)
