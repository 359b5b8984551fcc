"""Engine (serving/engine.py): ``stats["host_syncs"]`` over engine
iterations, both counted inside the window."""


def read(run):
    w = run.window
    return w.host_syncs / w.iterations if w.iterations else None
