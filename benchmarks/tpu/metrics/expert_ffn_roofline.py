"""Kernels (kernels/streamed_moe.py): the least time of the expert work
the routing asked for (6*d*d_expert FLOPs per routed pair; bytes of the
experts routed at least one row, and the routed rows in and out) over
the device time of the expert FFN's events, in percent."""
import windowstats as ws
import work

# The expert FFN's op events on the device, as a hand-read trace of
# both cells shows them (PERF.md): the Pallas kernel's custom calls in
# the prefill and decode programs (the only Pallas kernel that serving
# runs), and the fusions that slice the layer's routed-expert weights
# out of their stacked arrays for it, which is where those weights are
# read from HBM.  Shared experts (``moe____shared____``) are not
# matched.
KERNEL = r'custom_call_target="tpu_custom_call"|moe____w_(gate|up|down)__'


def read(run):
    r = run.reduced
    if r is None:
        return None
    least = ws.expert_least_seconds(run)
    t = r.kernel_seconds(KERNEL)
    if least is None or t is None:
        return None
    return work.share_percent(least, t)
