"""Helper the engine-telemetry readers share: the engine telemetry the
program attached to the window's placements."""


def telemetry(ctx):
    """The ``search_stats.telemetry`` of each placement that has one."""
    out = []
    for r in ctx["placements"]:
        tel = getattr(r["result"].search_stats, "telemetry", None)
        if tel is not None:
            out.append(tel)
    return out


def match_rounds(ctx):
    """``(matching rounds summed over the window, placements read)``."""
    tels = telemetry(ctx)
    return sum(int(t.match_rounds.sum()) for t in tels), len(tels)
