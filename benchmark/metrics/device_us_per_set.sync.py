"""device_us_per_set.sync: device-busy microseconds per signature set
answered in the traced part of the window (profiler trace: the union of
device operation intervals, over the sets whose verdict came in it)."""


def read(ctx):
    t = ctx["trace"]
    if not t or not ctx["sets_traced"]:
        return None
    return 1e6 * t["busy_s"] / ctx["sets_traced"]
