"""descent_ms: the host-driven greedy descent through the upper HNSW levels
per batch (``search.descent`` spans: row upload, the ``_greedy_level``
program, the copy back).  Entry descent (core/search.descend_entry)."""
from bench import stages


def read(ctx):
    return stages.per_batch_ms(ctx, ["search.descent"])
