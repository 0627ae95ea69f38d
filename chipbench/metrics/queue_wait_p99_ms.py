"""Front end (ServingLoop, DeadlineBatcher): 99th percentile, in ms, of
the wait from a request's due time to the dispatch of the plan that
carries its last jet (the proxy's dispatch spans)."""

from chipbench import measures


def read(run):
    return measures.queue_wait_p99_ms(run)
