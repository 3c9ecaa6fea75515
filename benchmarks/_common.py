"""Shared benchmark plumbing: provenance metadata + tracer breakdowns.

Every ``BENCH_*.json`` goes through :func:`write_bench`, which stamps
the payload with a ``meta`` block (schema version, jax backend and
version, git SHA, timestamp) so archived results are comparable across
machines and commits, and — when the global tracer is enabled (the
``benchmarks.run`` harness turns it on) — a ``span_breakdown`` block
with per-span-name wall-time aggregates (the per-kernel-form timing
split: plan.lower vs plan.construct vs plan.refine vs vcycle.refine
etc.).
"""

from __future__ import annotations

import json
import subprocess
import time

BENCH_SCHEMA_VERSION = 2


def _git_sha() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True,
            text=True, timeout=5, check=True).stdout.strip()
    except Exception:
        return "unknown"


def bench_metadata() -> dict:
    import jax

    from repro.runtime.device import pallas_interpret
    return {
        "schema_version": BENCH_SCHEMA_VERSION,
        "backend": jax.default_backend(),
        "pallas_interpret": pallas_interpret(),
        "jax_version": jax.__version__,
        "git_sha": _git_sha(),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
    }


def write_bench(payload: dict, out: str) -> dict:
    """Stamp ``payload`` with provenance metadata (and the tracer's span
    breakdown when spans were recorded), then write it to ``out``."""
    from repro.obs import get_tracer, span_breakdown
    payload = dict(payload)
    payload["meta"] = bench_metadata()
    tracer = get_tracer()
    if len(tracer):
        payload["span_breakdown"] = span_breakdown(tracer.spans())
    with open(out, "w") as fh:
        json.dump(payload, fh, indent=2)
    return payload
