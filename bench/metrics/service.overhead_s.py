"""Service layer (``launch/serve.py``): seconds per placement between the
client's submit and its answer that ``plan.execute`` does not cover --
queueing, the tick's grouping and content hashing, and handing the
result back.  Client clock minus program spans."""

from _spans import named


def read(ctx):
    execs = named(ctx, "plan.execute")
    done = ctx["placements"]
    if not execs or not done:
        return None
    latency = sum(r["latency_s"] for r in done) / len(done)
    return max(latency - sum(s["dur"] for s in execs) / len(execs), 0.0)
