"""Service layer (``launch/serve.py``): seconds per placement a request
waits before its tick starts -- queueing and the straggler wait of
``MappingService._gather`` -- from the ``service.queue`` span the worker
records per request.  Host clock, program spans."""

from _spans import named, per_placement


def read(ctx):
    spans = named(ctx, "service.queue")
    return per_placement(ctx, sum(s["dur"] for s in spans)) if spans \
        else None
