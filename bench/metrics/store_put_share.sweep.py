"""Share of the window's wall time under ``store.put`` spans: writing
each completed cell's metrics to the cell store (write and rename)."""


def read(ctx):
    from bench.lib.readers import outside_share, spans

    if not spans(ctx, "store.put"):
        return None
    outside = outside_share(ctx, ("store.put",))
    return None if outside is None else 100.0 - outside
