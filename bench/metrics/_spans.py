"""Helpers the per-layer metric readers share: sums and self times of
the program's spans in the measured window."""


def named(ctx, name):
    return [s for s in ctx["spans"] if s["name"] == name]


def per_placement(ctx, seconds):
    """``seconds`` spread over the window's placements, or ``None``."""
    n = len(ctx["placements"])
    return seconds / n if n and ctx["spans"] else None


def self_time(ctx, name):
    """Summed duration of the ``name`` spans minus the parts of them that
    their direct children (same thread, one level deeper) cover."""
    total = 0.0
    spans = ctx["spans"]
    for s in named(ctx, name):
        end = s["t0"] + s["dur"]
        child = sum(c["dur"] for c in spans
                    if c["tid"] == s["tid"] and c["depth"] == s["depth"] + 1
                    and c["t0"] >= s["t0"] and c["t0"] + c["dur"] <= end)
        total += s["dur"] - child
    return total
