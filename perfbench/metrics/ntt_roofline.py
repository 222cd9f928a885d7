"""Share of the NTT kernel's roofline: the least time HBM needs for the
bytes its launches move (``kernels.ntt_bytes``), over the device time of
its operations in the trace.  The bound is the bytes one: the integer
throughput of the TPU's vector unit has no published peak."""

import kernels

#: the kernel's custom call, as the TPU trace names it (the twiddle
#: table that the same jit builds before it is separate XLA work)
PATTERN = r"^%ntt_pallas(\.\d+)? = .*custom-call"


def read(ctx):
    if ctx.reduction is None or not ctx.launches:
        return None
    device_s = ctx.reduction.op_seconds(PATTERN)
    if device_s <= 0:
        return None
    least = sum(kernels.least_seconds(kernels.ntt_bytes(b, n), ctx.peaks)
                for b, n in ctx.launches)
    return 100.0 * least / device_s
