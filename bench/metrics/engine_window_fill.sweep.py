"""How full the engine's window is: the mean, over the window's chunk
calls weighted by their scan steps, of the active jobs (queued and
running, the largest lane's count, as the first ``sweep.peek`` of the
call read it into its ``active`` arg) over the call's window of ``W``
slots, in percent.  None where no span carries ``active``."""


def read(ctx):
    from bench.lib.readers import ENGINE_SPANS, spans

    calls = sorted(spans(ctx, "sweep.peek", *ENGINE_SPANS),
                   key=lambda s: s["ts"])
    active, num, den = None, 0.0, 0
    for s in calls:
        if s["name"] == "sweep.peek":
            if "active" in s["args"]:
                active = int(s["args"]["active"])
        elif active is not None:
            steps = int(s["args"].get("scan_steps", 0))
            num += steps * active / int(s["args"]["window"])
            den += steps
            active = None
    return 100.0 * num / den if den else None
