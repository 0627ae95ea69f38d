"""Model step (the whole jitted step: node-major transpose and pad, then
the kernel): model FLOPs of the valid jets served, over the step
programs' device time at the chip's peak, in %."""

from chipbench import measures


def read(run):
    return measures.step_mfu(run)
