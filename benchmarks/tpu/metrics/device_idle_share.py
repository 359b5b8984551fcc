"""Device (TPU): 1 - the union of device-op intervals over the traced
window, in percent."""


def read(run):
    r = run.reduced
    if r is None or r.busy_s is None or r.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.busy_s / r.window_s)
