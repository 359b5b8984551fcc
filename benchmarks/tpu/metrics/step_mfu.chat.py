"""``step_mfu`` in the open-loop cell, where it moves the inter-token
tail rather than tokens per second."""
import windowstats as ws


def read(run):
    return ws.step_mfu(run)
