"""device_wait_ms: time the batcher thread blocked on the search program
per batch (``search.wait`` spans: until ``_search_batch`` ends, plus the
copy back).  Search loop, host side (index/backends local searcher)."""
from bench import stages


def read(ctx):
    return stages.per_batch_ms(ctx, ["search.wait"])
