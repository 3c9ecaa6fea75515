"""Refine stage of the plan (``plan.refine`` spans): seconds per
placement of candidate-pair generation (``core/local_search.py``),
upload, the engine's device call, readback and the engine's host float64
objective.  Host clock, program spans."""

from _spans import named, per_placement


def read(ctx):
    return per_placement(ctx, sum(s["dur"] for s in named(ctx,
                                                          "plan.refine")))
