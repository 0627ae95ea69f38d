"""Kernel (kernels/fused_jedinet/full_kernel.py, the fused_full path):
share of its roofline, in %.  The least time for every plan's call at
its bucket (FLOPs over the chip's bf16 peak or bytes over HBM
bandwidth, whichever is larger) over the kernel's device time in the
trace.  The HLO op is the custom call named after the jitted wrapper
fused_forward_full."""

from chipbench import measures

KERNEL = "fused_forward_full"


def read(run):
    got = measures.kernel_roofline(run, KERNEL)
    if got is None:
        return None
    share, bound = got
    return {"value": share, "bound": bound}
