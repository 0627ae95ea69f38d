"""Front end: share of the window, in %, that the open-loop generator
sat inside single calls into the serving loop longer than 2 ms
(backpressure waits on the device, late completion notices from the
runtime, full garbage collections): time in which no request could be
submitted on schedule."""

from chipbench import measures


def read(run):
    return measures.loop_blocked_share(run)
