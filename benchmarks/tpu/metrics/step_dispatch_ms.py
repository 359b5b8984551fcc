"""Model step: the ``engine.dispatch`` spans (building each jitted
program's arguments and calling it) per engine iteration, in ms."""
import spanstats


def read(run):
    return spanstats.per_iter_ms(run, spanstats.DISPATCH)
