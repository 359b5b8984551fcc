"""Front end (serving/scheduler.py): p90 of admission into an engine
slot - due time, over the requests due in the window."""
import windowstats as ws


def read(run):
    p = ws.percentile(ws.queue_waits(run), 90)
    return None if p is None else 1e3 * p
