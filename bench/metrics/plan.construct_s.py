"""Construction layer (``core/construction.py``): seconds per placement
in the ``plan.construct`` spans (the host's initial mapping).  Host
clock, program spans."""

from _spans import named, per_placement


def read(ctx):
    return per_placement(ctx, sum(s["dur"] for s in named(ctx,
                                                          "plan.construct")))
