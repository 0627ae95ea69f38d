"""Device: share of the traced window, in %, in which no op ran on the
chip (mean over the cell's chips), from the profiler trace."""

from chipbench import measures


def read(run):
    return measures.device_idle_share(run)
