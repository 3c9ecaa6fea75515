"""Engine layer: device seconds per placement of the engine's refine
executable, from the profiler trace.  JAX names the module after the
jitted function, ``RefinementEngine``'s sweep loop ``refine_fn``, so its
runs appear on the trace's ``XLA Modules`` line as ``jit_refine_fn``."""

MODULE = "jit_refine_fn"


def read(ctx):
    dt = ctx["trace"]
    n = len(ctx["placements"])
    if dt is None or not n:
        return None
    secs, count = dt.total("modules", lambda name: MODULE in name)
    return secs / n if count else None
