"""fee_kernel_share: device time of the FEE kernels over device busy
time, from the profiler trace.  FEE kernels (kernels/fee_distance)."""


def read(ctx):
    d = ctx.device
    if d is None or not d.busy_s or not d.fee_calls:
        return None
    return d.fee_s / d.busy_s
