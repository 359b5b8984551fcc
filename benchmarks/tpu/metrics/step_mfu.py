"""Model step: the least time the chip needs for the work the window's
routing and requests asked for (the larger of FLOPs over the bf16 peak
and bytes over HBM bandwidth, per program call), summed, over the
window's seconds, in percent."""
import windowstats as ws


def read(run):
    return ws.step_mfu(run)
