"""sets_per_s: signature sets whose block verdict arrived inside the
window, over the window's seconds (host clock)."""


def read(ctx):
    return ctx["sets_in_window"] / ctx["window_s"]
