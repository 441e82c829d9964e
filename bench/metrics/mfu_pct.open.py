"""Dense int8 operations of the frames served over fleet-step wall time times
the chips' int8 peak, in %."""
from bench.readers import mfu_pct


def read(run):
    return mfu_pct(run)
