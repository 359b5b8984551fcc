"""Model step: mean host time of the window's ``Scheduler.step`` calls
that ran a prefill chunk."""
import windowstats as ws


def read(run):
    return ws.mean_step_ms(run, lambda s: bool(s.prefill))
