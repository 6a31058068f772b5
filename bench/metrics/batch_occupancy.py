"""batch_occupancy: real requests over padded batch rows, summed over the
batches formed in the window (the batcher's ``bucket_pad`` spans carry
``n`` and ``bucket``).  Front end (repro.serve batcher)."""
from bench import stats


def read(ctx):
    batches = stats.per_batch(stats.window_spans(ctx, "bucket_pad"))
    rows = sum(s.attrs["bucket"] for s in batches)
    return sum(s.attrs["n"] for s in batches) / rows if rows else None
