"""Model step: mean host time of the window's decode-only
``Scheduler.step`` calls (each ends in a host fetch, so it is
synchronous)."""
import windowstats as ws


def read(run):
    return ws.mean_step_ms(run, lambda s: s.decode and not s.prefill)
