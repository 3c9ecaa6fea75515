"""Executables compiled (or loaded from the persistent cache) inside the
measured window, counted by ``jax.monitoring`` (``compile_events.py``).
Every shape the window uses is warmed first, so this should read 0."""


def read(ctx):
    return ctx["compiles"]
