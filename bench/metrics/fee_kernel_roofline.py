"""fee_kernel_roofline: the FEE kernels' share of their HBM roofline, in %.

Bytes come from shapes (``bench.roofline.fee_call_bytes``): per call the
batch's queries plus every compacted lane's full stored row, as if no lane
exited early.  Time is the kernels' device time in the profiler trace; the
peak is ``bench/peaks.json``'s HBM bandwidth for this device.  FEE kernels
(kernels/fee_distance).  Memory-bound: the kernels do about one FLOP per
byte, far below the chip's balance point."""
from bench import roofline


def read(ctx):
    d = ctx.device
    if d is None or not d.fee_calls or not d.fee_s:
        return None
    nbytes = sum(roofline.fee_call_bytes(ctx.config, b, ctx.shapes) * n
                 for b, n in d.fee_calls.items())
    return 100.0 * nbytes / (d.fee_s * ctx.peaks["hbm_bytes_per_s"])
