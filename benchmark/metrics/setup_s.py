"""setup_s: seconds from process start to the window's start: device
init, table, traffic plan, waiting for the signatures, and the warm-up
that traces, lowers and loads (or, cold, compiles) every program."""


def read(ctx):
    return ctx["setup_s"]
