"""Engine layer (``engine/sweep.py``): sweeps per placement the device
engine applied (``search_stats.objective_trace``).  Fewer sweeps under
the same budget means the search stopped at a local optimum."""


def read(ctx):
    done = [r for r in ctx["placements"]
            if r["result"].search_stats is not None]
    if not done:
        return None
    return sum(len(r["result"].search_stats.objective_trace) - 1
               for r in done) / len(done)
