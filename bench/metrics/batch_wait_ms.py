"""batch_wait_ms: time the batcher thread waited for a batch to form, per
batch: ``serve.take`` spans that took at least one request (the linger
included; empty polls of an idle queue left out).  Front end (queue):
repro.serve queue."""
from bench import stages


def read(ctx):
    return stages.per_batch_ms(
        ctx, ["serve.take"], keep=lambda s: (s.attrs or {}).get("n", 0) > 0)
