"""Least time for the frames served in the traced window (bench/work.py) over
the device busy time, in %."""
from bench.readers import device_roofline_pct


def read(run):
    return device_roofline_pct(run)
