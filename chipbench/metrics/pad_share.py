"""Front end: padding rows over all rows dispatched, in %, from the
buckets and valid rows of the plans the batcher cut."""

from chipbench import measures


def read(run):
    return measures.pad_share(run)
