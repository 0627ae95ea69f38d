"""Load generator: 99th percentile of (submit - due), in ms, over the
window's requests.  A late generator is not a fast server."""

from chipbench import measures


def read(run):
    return measures.gen_lag_p99_ms(run)
