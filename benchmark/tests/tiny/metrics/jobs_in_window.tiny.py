"""jobs_in_window.tiny: service jobs timed in the window, sampled from
the node just before and just after it (a reader added by a file alone)."""


def sample(node):
    return len(node["service"].job_timings())


def read(ctx):
    before, after = ctx["samples"]["jobs_in_window.tiny"]
    return after - before or None
