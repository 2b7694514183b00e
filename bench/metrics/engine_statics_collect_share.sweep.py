"""Share of the window's wall time under ``sweep.statics`` and
``sweep.collect`` spans: the engine's work around its chunk loop, the
lane batch's compile statics (host reads of whole lane arrays and the
peak-active bound) before it and the final state's read-back after it."""

NAMES = ("sweep.statics", "sweep.collect")


def read(ctx):
    from bench.lib.readers import outside_share, spans

    if not spans(ctx, *NAMES):
        return None
    outside = outside_share(ctx, NAMES)
    return None if outside is None else 100.0 - outside
