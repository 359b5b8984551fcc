"""Output tokens emitted in the window over the window's seconds."""
import windowstats as ws


def read(run):
    n = ws.output_tokens(run)
    return n / run.window.seconds if n else None
