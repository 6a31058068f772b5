"""hop_utilization: ``search.hops`` over ``search.hop_slots`` from the
program's default registry, counted over the window: the share of the hop
iterations the vmapped search loop ran that some lane needed (a batch runs
until its slowest lane stops).  Search loop (core/search)."""


def read(ctx):
    slots = ctx.counters.get("search.hop_slots", 0.0)
    return ctx.counters.get("search.hops", 0.0) / slots if slots else None
