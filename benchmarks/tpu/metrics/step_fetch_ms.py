"""Engine (serving/engine.py): the ``engine.fetch`` spans (each blocking
device-to-host read: the wait for the device and the transfer) per
engine iteration, in ms."""
import spanstats


def read(run):
    return spanstats.per_iter_ms(run, spanstats.FETCH)
