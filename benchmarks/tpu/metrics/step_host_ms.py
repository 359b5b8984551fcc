"""Engine (serving/engine.py): the serving loop's own host work per
engine iteration, in ms: each ``sched.step`` span less the part of it
inside ``engine.dispatch`` or ``engine.fetch``."""
import spanstats


def read(run):
    return spanstats.step_host_ms(run)
