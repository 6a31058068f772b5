"""resolve_ms: building and handing out the answers per batch
(``serve.resolve`` spans: residual/FEE accounting, top-k slices,
``Response`` objects, futures).  Front end (repro.serve batcher)."""
from bench import stages


def read(ctx):
    return stages.per_batch_ms(ctx, ["serve.resolve"])
