"""Mean host wall time of one chunk call's asynchronous enqueue
(``sweep.dispatch`` spans, inside ``sweep.compile``/``sweep.execute``),
in microseconds."""


def read(ctx):
    from bench.lib.readers import spans

    ss = spans(ctx, "sweep.dispatch")
    if not ss:
        return None
    return 1e6 * sum(s["dur"] for s in ss) / len(ss)
