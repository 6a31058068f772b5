"""Per-batch times of the served path's stages, from the program's live
stage spans on the batcher thread (``serve.take`` ... ``serve.resolve``,
one of each per batch; ``search.descent`` once per upper level).

A batch is one ``search.wait`` span: the one stage every executed batch
has exactly once.  A program without these spans gives ``None``."""
from bench import stats

# every stage span except the queue wait (serve.take) and the device wait
# (search.wait): the host's own work per batch
HOST_STAGES = ("serve.admit", "serve.pad", "search.pca", "search.descent",
               "search.dispatch", "search.count", "serve.resolve")


def per_batch_ms(ctx, names, keep=None):
    """Summed duration of the window's spans called any of ``names`` (those
    that ``keep`` accepts), over the window's batches, in ms."""
    batches = len(stats.window_spans(ctx, "search.wait"))
    if not batches:
        return None
    ns = sum(s.dur_ns for name in names for s in stats.window_spans(ctx, name)
             if keep is None or keep(s))
    return ns / batches / 1e6
