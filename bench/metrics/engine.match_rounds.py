"""Engine layer (``engine/sweep.py``): greedy-matching rounds per
placement, summed over the gain passes of the engine telemetry
(``search_stats.telemetry.match_rounds``), which the program collects
whenever its tracer records.  Fewer rounds for the same sweeps is less
matching-loop work."""

from _telemetry import match_rounds


def read(ctx):
    total, placements = match_rounds(ctx)
    return total / placements if placements else None
