"""search_loop_ms: device time of the ``_search_batch`` program per
execution, from the profiler trace.  Search loop (core/search)."""


def read(ctx):
    d = ctx.device
    if d is None or not d.search_runs:
        return None
    return d.search_s / d.search_runs * 1e3
