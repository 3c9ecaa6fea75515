"""Plan layer (``core/plan.py``): self time of ``plan.execute`` per
placement -- what it does outside its ``plan.construct`` and
``plan.refine`` children: the device objective of the constructed and
of the final mapping, and result assembly.  Host clock, program spans."""

from _spans import per_placement, self_time


def read(ctx):
    return per_placement(ctx, self_time(ctx, "plan.execute"))
