"""Engine layer (``engine/sweep.py:RefinementEngine``): seconds per
placement of the ``engine.upload`` spans -- the device graph's build and
bucket padding, the candidate-pair upload and the runtime toggles, with
their upload caches.  Host clock, program spans."""

from _spans import named, per_placement


def read(ctx):
    spans = named(ctx, "engine.upload")
    return per_placement(ctx, sum(s["dur"] for s in spans)) if spans \
        else None
