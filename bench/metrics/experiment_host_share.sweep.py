"""Share of the window's wall time under the experiment layer's host
spans: ``experiment.*`` (fingerprints, store reads, lane building, the
per-chunk metrics and store flush), ``trace.generate``, ``scenario.apply``
and ``store.put``.  None where the program has no ``experiment.metrics``
span, as its result handling is then in no span."""

NAMES = ("trace.generate", "scenario.apply", "store.put")


def read(ctx):
    from bench.lib.readers import outside_share, spans

    if not spans(ctx, "experiment.metrics"):
        return None
    names = {s["name"] for s in ctx.spans
             if s["name"].startswith("experiment.")} | set(NAMES)
    outside = outside_share(ctx, tuple(names))
    return None if outside is None else 100.0 - outside
