"""recall_at_10: share of the exact top-10 ids (bench.reference, over the
raw float32 corpus) found in the ok answers of the window, computed by the
benchmark on the host after the window."""


def read(ctx):
    return ctx.answers.recall
