"""setup_s: seconds from process start to the first request of the window.

Corpus generation, index build or load, device upload, program compile (or
compile-cache load), the server's warm-up and the cell's own warm-up
traffic.  Host clock."""


def read(ctx):
    return ctx.setup_s
