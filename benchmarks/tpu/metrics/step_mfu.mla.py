"""Model step, for a DeepSeek-V3-block configuration: the least time the
chip needs for the work the window's routing and requests asked for
(latent attention over the latent cache, leading dense layers, MoE
layers; ``work_mla.py``), summed per program call, over the window's
seconds, in percent."""
import work
import work_mla


def read(run):
    least = work_mla.required_step_seconds(run)
    if least is None:
        return None
    return work.share_percent(least, run.window.seconds)
