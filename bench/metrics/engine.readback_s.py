"""Engine layer (``engine/sweep.py:RefinementEngine``): seconds per
placement of the ``engine.readback`` spans -- the output transfers after
the device finished (``engine.wait``) and the engine's host float64
objective in ``_stats``.  Host clock, program spans."""

from _spans import named, per_placement


def read(ctx):
    spans = named(ctx, "engine.readback")
    return per_placement(ctx, sum(s["dur"] for s in spans)) if spans \
        else None
