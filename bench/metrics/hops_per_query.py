"""hops_per_query: ``search.hops`` over ``search.queries`` from the
program's default registry, counted over the window.  Both counters include
the padding rows of each batch.  Search loop (core/search)."""


def read(ctx):
    q = ctx.counters.get("search.queries", 0.0)
    return ctx.counters.get("search.hops", 0.0) / q if q else None
