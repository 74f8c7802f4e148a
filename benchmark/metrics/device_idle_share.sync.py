"""device_idle_share.sync: share of the traced window in which no
operation ran on the device (profiler trace, benchmark/trace_reduce.py)."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["window_s"]:
        return None
    return 1.0 - t["busy_s"] / t["window_s"]
