"""Share of the window's wall time under ``sweep.peek`` spans: the engine
loop's host control flow between chunk calls (the active-set read, the
window-ladder and warm-program choice, the read of the tick counters),
through which the device idles."""


def read(ctx):
    from bench.lib.readers import outside_share, spans

    if not spans(ctx, "sweep.peek"):
        return None
    outside = outside_share(ctx, ("sweep.peek",))
    return None if outside is None else 100.0 - outside
