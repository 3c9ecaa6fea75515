"""Documented host<->device boundaries.

The sanitizer CI lane runs tier-1 under ``JAX_TRANSFER_GUARD=disallow``,
which rejects every *implicit* transfer — np arrays flowing into jitted
functions, ``np.asarray``/``int()`` readbacks, Python-scalar promotion
in eager ops.  The library's real boundaries (graph upload, engine
dispatch/readback, objective readback, coarsening rebuilds) are
deliberate, so they scope a ``jax.transfer_guard("allow")`` via
:func:`host_boundary`.  Anything *outside* one of these scopes that
transfers under the sanitizer lane is a bug, which is exactly the
point.

The static checker honors the same marker: VIEM001's transfer findings
are exempt inside a ``with host_boundary(...)`` block, so the lint rule
and the runtime guard enforce one shared notion of "documented
boundary".
"""

from __future__ import annotations

import contextlib

__all__ = ["host_boundary"]


@contextlib.contextmanager
def host_boundary(tag: str, span: bool = False):
    """Mark a deliberate host<->device transfer site.

    ``tag`` names the boundary in the style of a metrics key
    (``"engine.readback"``, ``"graph.upload"``) — it documents intent at
    the call site and gives grep one vocabulary for every crossing.
    With ``span``, the crossing is also a tracer span of that name
    (:mod:`repro.obs.trace`), yielded so the caller can attach
    attributes; otherwise the block gets ``None``.
    """
    import jax
    if not span:
        with jax.transfer_guard("allow"):
            yield None
        return
    from ..obs.trace import get_tracer
    with get_tracer().span(tag) as sp, jax.transfer_guard("allow"):
        yield sp
