"""Refine stage (``core/plan.py:MappingPlan._pairs``): seconds per
placement of candidate-pair generation (``core/local_search.py``) and
its LRU, from the ``plan.pairs`` spans inside ``plan.refine``.  Host
clock, program spans."""

from _spans import named, per_placement


def read(ctx):
    spans = named(ctx, "plan.pairs")
    return per_placement(ctx, sum(s["dur"] for s in spans)) if spans \
        else None
