"""host_path_ms: the batcher thread's own work per batch: every stage span
but the queue wait and the device wait (admission, padding, PCA, entry
descent, dispatch, counters, resolve).  Host path (repro.serve batcher,
index/backends local searcher)."""
from bench import stages


def read(ctx):
    return stages.per_batch_ms(ctx, stages.HOST_STAGES)
