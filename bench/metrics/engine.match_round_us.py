"""Engine layer (``engine/sweep.py``): device microseconds per round of
the greedy matching loop -- the loop's device time in the window over
the window's matching rounds (engine telemetry, as ``engine.match_rounds``
reads it).

The trace names operations by bare HLO text, with no source metadata,
so the loop is found by its structure: among the ``while`` operations
that start inside a ``jit_refine_fn`` module run, leave out the
outermost (the sweep loop, one event per run) and take the one with the
most device time.  Each gain pass runs it once, so its event count
should equal the window's gain passes."""

import bisect

from _telemetry import match_rounds
from xplane import short_name

MODULE = "jit_refine_fn"


def is_while(name):
    return " while(" in name


def matching_loop(dt):
    """``(short name, seconds, events)`` of the matching loop, seconds
    and events averaged over devices, or ``None``."""
    acc = {}
    devs = dt.devices()
    for dev in devs:
        runs = sorted((t0, t0 + dur) for name, t0, dur
                      in dt.modules.get(dev, ()) if MODULE in name)
        starts = [t0 for t0, _ in runs]
        # by start, an enclosing loop before the loops it holds
        loops = sorted(((t0, dur, name) for name, t0, dur in dt.ops[dev]
                        if is_while(name)), key=lambda e: (e[0], -e[1]))
        outer_end = None
        for t0, dur, name in loops:
            i = bisect.bisect_right(starts, t0) - 1
            if i < 0 or t0 > runs[i][1]:
                continue                      # not in a refine run
            if outer_end is None or t0 >= outer_end:
                outer_end = t0 + dur          # outermost: the sweep loop
                continue
            secs, cnt = acc.get(short_name(name), (0.0, 0))
            acc[short_name(name)] = (secs + dur, cnt + 1)
    if not acc:
        return None
    name, (secs, cnt) = max(acc.items(), key=lambda e: e[1][0])
    return name, secs / len(devs), cnt / len(devs)


def read(ctx):
    dt = ctx["trace"]
    total, _ = match_rounds(ctx)
    found = None if dt is None or not total else matching_loop(dt)
    return None if found is None else 1e6 * found[1] / total
