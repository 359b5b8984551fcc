"""Device (TPU): percent of the window in which no op runs on the device
while the host is inside ``sched.step`` and not inside ``engine.fetch``:
the idle that the loop's own dispatch and host work leave."""
import spanstats


def read(run):
    return spanstats.idle_host_share(run)
