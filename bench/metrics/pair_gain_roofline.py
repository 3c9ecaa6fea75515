"""Kernel layer (``kernels/pair_gain.py``): share of its roofline the
Pallas pair-gain kernel reaches, in percent: the least time its calls
could take over their device time.

Each sweep makes two calls, one per side of the candidate pairs.  On the
trace a call is a ``tpu_custom_call`` operation of the refine executable
whose result is one float32 gain per pair, ``f32[P,1]`` (the objective
kernel's result is ``f32[1,1]``).  P is read from that shape; K is the
plan's neighbour-row width, and the table type that of the plan's
``KernelConfig.dist_dtype`` (the int8/int16 packing of a matrix-form
table); ``kernel_cost.py`` turns (form, P, K, table type) into least
bytes and operations, and ``peaks.json`` into seconds."""

import re

import kernel_cost

CALL = re.compile(r"= f32\[(\d+),1\]\{[^}]*\} custom-call\(.*"
                  r'custom_call_target="tpu_custom_call"')
_AXES = {"torus": lambda m: len(m["dims"]),
         "tree": lambda m: len(m["factors"]), "matrix": lambda m: 0}


def read(ctx):
    dt, peaks = ctx["trace"], ctx["peaks"]
    if dt is None or peaks is None or not ctx["buckets"]:
        return None
    machine = ctx["config"]["machine"]
    kind = machine["kind"]
    k = ctx["buckets"][0].max_deg
    dist_dtype = getattr(ctx.get("kernel_config"), "dist_dtype", None)
    secs = nbytes = ops = 0.0
    for dev in dt.devices():
        for name, _, dur in dt.ops[dev]:
            m = CALL.search(name)
            if m is None or int(m.group(1)) < 2:
                continue
            p = int(m.group(1))
            secs += dur
            nbytes += kernel_cost.side_bytes(kind, p, k, dist_dtype)
            ops += kernel_cost.side_ops(kind, p, k, _AXES[kind](machine))
    if secs <= 0:
        return None
    least, _bound = kernel_cost.least_seconds(nbytes, ops, peaks)
    return 100.0 * least / secs
