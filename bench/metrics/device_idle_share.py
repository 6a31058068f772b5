"""device_idle_share: 1 - (union of device operation intervals) over the
traced window, from the profiler trace.  Device."""


def read(ctx):
    d = ctx.device
    if d is None or not d.window_s or not d.busy_s:
        return None
    return 1.0 - d.busy_s / d.window_s
