"""qps: ok answers completed inside the window, over the window's seconds.

Closed-loop cells.  Counts every completion in the window, whichever phase
sent the request.  Host clock."""


def read(ctx):
    done = sum(1 for r in ctx.records
               if r.ok and ctx.t0 <= r.done <= ctx.t_end)
    return done / ctx.seconds
