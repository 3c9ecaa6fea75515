"""The jitted sweep loop and its session wrapper (see package docstring).

``_make_refine`` builds the pure device function — one ``lax.while_loop``
from initial permutation to converged permutation — for one distance form;
:class:`RefinementEngine` wraps it with host glue: DeviceGraph/pair
conversion (cached per graph structure), jit/vmap executables (cached per
shape by jax), eps selection, and :class:`SearchStats` reporting against
host float64 objectives.
"""

from __future__ import annotations

from collections import OrderedDict
from contextlib import contextmanager
from dataclasses import dataclass

import numpy as np

from ..core.graph import CommGraph, DeviceGraph, device_pairs
from ..core.local_search import SearchStats
from ..core.objective import qap_objective
from ..obs.trace import get_tracer
from ..runtime.boundary import host_boundary

_TR = get_tracer()

# Gain/acceptance threshold relative to |J0|: must sit above the f32
# noise of the device objective (~1e-7 · J0 for the edge-sum) while not
# swallowing genuine gains — 1e-6 converges to the same optima as exact
# thresholds on every benchmarked workload (see BENCH_engine.json).
_EPS_REL = 1e-6


def _greedy_matching(g_m, pos, us, vs, n: int):
    """Greedy maximal matching over the pairs ``(us, vs)`` by gain
    priority: rounds of locally dominant eligible pairs (highest gain at
    both endpoints, ties → lowest index) until no eligible pair is left —
    the parallel equivalent of popping a gain-ordered priority queue
    while skipping used vertices.  ``pos`` marks the pairs that may be
    matched at all, ``g_m`` their gains.

    Returns ``(sel, used, claim, rounds)``: the selected pair mask, the
    matched vertices, the claim map (int32[n], the index of the pair that
    matched each vertex, ``P`` where none did) and the round count.  The
    vertex side comes from the claim map, never from scattering the pair
    masks (see :func:`_make_refine`)."""
    import jax
    import jax.numpy as jnp
    p = us.shape[0]
    idx = jnp.arange(p, dtype=jnp.int32)

    def match_round(mstate):
        sel, used, elig, claim, rounds = mstate
        ge = jnp.where(elig, g_m, -jnp.inf)
        vmax = jnp.full((n,), -jnp.inf, jnp.float32)
        vmax = vmax.at[us].max(ge).at[vs].max(ge)
        cand = elig & (ge >= vmax[us]) & (ge >= vmax[vs])
        vmin = jnp.full((n,), p, jnp.int32)
        masked_idx = jnp.where(cand, idx, p)
        vmin = vmin.at[us].min(masked_idx).at[vs].min(masked_idx)
        new = cand & (vmin[us] == idx) & (vmin[vs] == idx)
        # a new pair holds vmin at both endpoints: v is matched this round
        # exactly when vmin[v] names a new pair (index P reads False)
        got = new.at[vmin].get(mode="fill", fill_value=False)
        used = used | got
        elig = elig & ~used[us] & ~used[vs]
        return (sel | new, used, elig, jnp.where(got, vmin, claim),
                rounds + 1)

    sel, used, _, claim, rounds = jax.lax.while_loop(
        lambda mstate: jnp.any(mstate[2]), match_round,
        (jnp.zeros((p,), jnp.bool_), jnp.zeros((n,), jnp.bool_), pos,
         jnp.full((n,), p, jnp.int32), jnp.int32(0)))
    return sel, used, claim, rounds


def _apply_claims(perm, used, claim, us, vs):
    """``perm`` with the matching applied: each matched vertex takes its
    partner's PE, ``other[v] = us[claim[v]] + vs[claim[v]] - v`` — n-long
    gathers only."""
    import jax.numpy as jnp
    vid = jnp.arange(perm.shape[0], dtype=jnp.int32)
    c = jnp.where(used, claim, 0)
    return perm[jnp.where(used, us[c] + vs[c] - vid, vid)]


def _make_refine(kind: str, params: tuple, max_sweeps: int,
                 use_pallas: bool = False, interpret: bool = False,
                 config=None):
    """The device sweep fn for one distance form.

    Signature: ``(nbr, wgt, eu, ev, ew, us, vs, perm0, D, eps, tenure,
    dlb, collect) -> (perm, trace, sweeps, swaps, tel)`` — all jnp, no
    host syncs inside; the trace is the carried objective after each
    sweep (NaN past convergence).  Monotone in its *result* by
    construction: every sweep
    either applies a greedy maximal matching verified (against the
    recomputed device objective) to beat the best single swap, or falls
    back to the best single pair with its exact incremental gain, and the
    returned permutation is the best one seen.

    ``tenure``/``dlb`` are RUNTIME scalars (int32 / bool) — tabu memory
    and don't-look bits compile into the same executable as the plain
    monotone sweep and are enabled by masking, never by retracing:

      * ``tenure > 0`` — a swapped candidate pair becomes tabu for that
        many sweeps (rejecting the immediate reversal), tabu pairs are
        masked out of selection unless they would beat the best-seen
        objective (aspiration), and when no positive-gain move remains
        the sweep takes the best *non-tabu* move even downhill — the
        robust-tabu-search escape from the local optima the monotone
        matching converges to (Paul, arXiv:1009.4880).  The sweep then
        runs to its budget; the best-seen permutation is returned.
      * ``dlb`` — vertices whose incident candidate pairs all had
        non-positive gain go *cold*; pairs with both endpoints cold are
        skipped until a nearby move (the vertex itself or an ELL
        neighbor) wakes them.  Selection-level only: gains are still
        computed (fixed shapes), cold regions just stop attracting moves.

    With ``tenure == 0`` and ``dlb == False`` every mask is identity and
    the loop is bit-for-bit the pre-tabu monotone sweep (tested).

    The matching (:func:`_greedy_matching`) keeps its vertex side in a
    claim map, ``claim[v]`` = the pair that matched ``v``, and scatters
    only the per-round ``vmax``/``vmin`` reductions.  Two identities make
    that exact: a pair is new in a round only if it holds ``vmin`` at both
    endpoints, so ``v`` is matched in that round iff ``vmin[v]`` names a
    new pair (one n-long gather, no scatter of the pair mask); and every
    selected pair has used endpoints, so the loop's eligibility mask,
    carried from round to round, is its whole exit test.  The swaps
    (:func:`_apply_claims`) and the don't-look wake set (the matched
    vertices, or the fallback pair's two) read ``used`` and ``claim``
    with n-long gathers in place of P-long scatters.

    ``collect`` is a RUNTIME bool enabling the engine telemetry carries
    (``tel`` — see :mod:`repro.obs.telemetry`): fixed-shape, pass-indexed
    counter arrays (exchanges applied, tabu-masked pairs, aspiration
    fires, matching rounds) plus downhill-escape and pass totals, all
    updated under a ``jnp.where(collect, ...)`` mask.  Same no-retrace
    discipline as the tabu knobs — toggling it shares the one compiled
    executable — and the counters never feed back into the search, so
    the ``(perm, trace, sweeps, swaps)`` outputs are bit-identical with
    collection on, off, or absent (tested).  Off, every counter is zero.
    """
    import jax
    import jax.numpy as jnp

    from ..kernels import pair_gain as pg

    def gains_of(nbr, wgt, perm, us, vs, D):
        if use_pallas:
            return pg.pair_gains_pallas(kind, params, nbr, wgt, perm,
                                        us, vs, D, interpret=interpret,
                                        config=config)
        return pg.pair_gains(kind, params, nbr, wgt, perm, us, vs, D,
                             config=config)

    def refine_fn(nbr, wgt, eu, ev, ew, us, vs, perm0, D, eps,
                  tenure, dlb, collect):
        refine_fn.traces += 1           # host-side: counts (re)traces only
        n = perm0.shape[0]
        p = us.shape[0]
        idx = jnp.arange(p, dtype=jnp.int32)
        tabu_on = tenure > 0
        neg_inf = jnp.float32(-jnp.inf)

        def objective(perm):
            return pg.edge_objective(kind, params, eu, ev, ew, perm, D,
                                     config=config)

        j0 = objective(perm0)
        trace0 = jnp.full((max_sweeps + 1,), jnp.nan,
                          jnp.float32).at[0].set(j0)

        def cond(state):
            return (~state["done"]) & (state["sweeps"] < max_sweeps)

        def body(state):
            perm, j, sweeps = state["perm"], state["j"], state["sweeps"]
            swaps, best_j = state["swaps"], state["best_j"]
            g = gains_of(nbr, wgt, perm, us, vs, D)
            # ---- tabu / don't-look masking (identity when both are off:
            # every `blocked` bit is False and g_m is g, bit-for-bit)
            aspire = (j - g) < best_j - eps     # would beat the best seen
            tabu_active = tabu_on & (state["tabu_until"] > sweeps)
            blocked = tabu_active & ~aspire
            blocked |= dlb & state["cold"][us] & state["cold"][vs]
            # under tabu the fallback may move downhill, so inert padding
            # pairs (u == v, gain 0) must never be "best" — mask them too
            blocked |= tabu_on & (us == vs)
            g_m = jnp.where(blocked, neg_inf, g)
            best = jnp.argmax(g_m)              # first max → lowest index
            gbest = g_m[best]
            any_pos = gbest > eps

            # ---- greedy maximal matching by gain priority, applied from
            # its claim map (each vertex in ≤ 1 selected pair)
            sel, used, claim, m_rounds = _greedy_matching(
                g_m, g_m > eps, us, vs, n)
            perm_m = _apply_claims(perm, used, claim, us, vs)
            j_m = objective(perm_m)             # device O(m) — swaps of a
            take = any_pos & (j_m < j - gbest)  # matching interact, verify

            # ---- fallback: the single best pair, exact incremental gain;
            # under tabu, with no positive gain left, the best *eligible*
            # pair is taken even downhill (the escape move) — padding and
            # fully-blocked states leave gbest at -inf, which ends the loop
            ub, vb = us[best], vs[best]
            perm_f = perm.at[ub].set(perm[vb]).at[vb].set(perm[ub])
            fall_down = tabu_on & ~any_pos & (gbest > neg_inf)
            fall = (any_pos & ~take) | fall_down
            moved = any_pos | fall_down

            perm_n = jnp.where(take, perm_m, jnp.where(fall, perm_f, perm))
            j_n = jnp.where(take, j_m, jnp.where(fall, j - gbest, j))
            swaps_n = swaps + jnp.where(
                take, jnp.sum(sel, dtype=jnp.int32),
                jnp.where(fall, jnp.int32(1), jnp.int32(0)))
            sweeps_n = jnp.where(moved, sweeps + 1, sweeps)
            trace_n = state["trace"].at[sweeps_n].set(j_n)

            # ---- tabu memory: pairs applied this sweep reject their
            # reversal for `tenure` sweeps
            applied = jnp.where(take, sel, (idx == best) & fall)
            tabu_until = jnp.where(applied & tabu_on, sweeps_n + tenure,
                                   state["tabu_until"])

            # ---- don't-look bits: a vertex with no positive incident
            # gain goes cold; a move wakes the endpoints and their ELL
            # neighbors (selection-level masking only — see docstring)
            warm = jnp.zeros((n,), jnp.int32)
            pos_raw = (g > eps).astype(jnp.int32)
            warm = warm.at[us].max(pos_raw).at[vs].max(pos_raw) > 0
            # moved: the matched vertices, or the fallback pair's two
            vid = jnp.arange(n, dtype=jnp.int32)
            moved_v = jnp.where(take, used,
                                fall & ((vid == ub) | (vid == vb)))
            wake = moved_v | jnp.any(moved_v[nbr] & (wgt > 0), axis=1)
            cold = jnp.where(wake, False, state["cold"] | ~warm)

            # ---- telemetry carries (repro.obs): pass-indexed counters,
            # masked by the runtime `collect` toggle — never read by the
            # search, so the outputs above are bit-identical either way
            pass_idx = sweeps                   # unique per body iteration
            exch = jnp.where(
                take, jnp.sum(sel, dtype=jnp.int32),
                jnp.where(fall, jnp.int32(1), jnp.int32(0)))

            def rec(key, val):
                return jnp.where(collect,
                                 state[key].at[pass_idx].set(val),
                                 state[key])

            tel_on = collect
            # ---- best-seen tracking (with tabu off, j is monotone and
            # best == current, bit-for-bit)
            improved = j_n < state["best_j"]
            return {
                "perm": perm_n, "j": j_n, "trace": trace_n,
                "sweeps": sweeps_n, "swaps": swaps_n, "done": ~moved,
                "best_perm": jnp.where(improved, perm_n,
                                       state["best_perm"]),
                "best_j": jnp.where(improved, j_n, state["best_j"]),
                "tabu_until": tabu_until, "cold": cold,
                "tel_exchanges": rec("tel_exchanges", exch),
                "tel_tabu_masked": rec(
                    "tel_tabu_masked",
                    jnp.sum(tabu_active & ~aspire, dtype=jnp.int32)),
                "tel_aspirations": rec(
                    "tel_aspirations",
                    jnp.sum(tabu_active & aspire, dtype=jnp.int32)),
                "tel_match_rounds": rec("tel_match_rounds", m_rounds),
                "tel_downhill": state["tel_downhill"] + jnp.where(
                    tel_on & fall_down, jnp.int32(1), jnp.int32(0)),
                "tel_passes": state["tel_passes"] + jnp.where(
                    tel_on, jnp.int32(1), jnp.int32(0)),
            }

        tel0 = jnp.zeros((max_sweeps + 1,), jnp.int32)
        state = {
            "perm": perm0, "j": j0, "trace": trace0,
            "sweeps": jnp.int32(0), "swaps": jnp.int32(0),
            "done": jnp.bool_(False), "best_perm": perm0, "best_j": j0,
            "tabu_until": jnp.zeros((p,), jnp.int32),
            "cold": jnp.zeros((n,), jnp.bool_),
            "tel_exchanges": tel0, "tel_tabu_masked": tel0,
            "tel_aspirations": tel0, "tel_match_rounds": tel0,
            "tel_downhill": jnp.int32(0), "tel_passes": jnp.int32(0),
        }
        out = jax.lax.while_loop(cond, body, state)
        tel = {
            "exchanges": out["tel_exchanges"],
            "tabu_masked": out["tel_tabu_masked"],
            "aspirations": out["tel_aspirations"],
            "match_rounds": out["tel_match_rounds"],
            "downhill_escapes": out["tel_downhill"],
            "passes": out["tel_passes"],
            "sweeps": out["sweeps"],
        }
        return (out["best_perm"], out["trace"], out["sweeps"],
                out["swaps"], tel)

    refine_fn.traces = 0
    return refine_fn


@dataclass
class EngineResult:
    """One device refinement: the final permutation plus host-facing
    stats (objectives in host float64; the trace is the device f32
    carry, one entry per applied sweep)."""
    perm: np.ndarray
    stats: SearchStats
    sweeps: int


class RefinementEngine:
    """Compiled sweep-loop executables for one machine topology.

    One instance per (``kernel_params()``, ``max_sweeps``,
    ``kernel_config``) — the Mapper keys its engine cache exactly so.
    jax re-specializes the jitted fn per array shape;
    :class:`DeviceGraph`/pair padding buckets shapes so same-shape graphs
    share one executable.  ``use_pallas`` routes the gain reduction
    through the hand-tiled Pallas kernel (default: only on real TPU
    backends; the fused-jnp path is best everywhere else).

    ``kernel_config`` (a :class:`~repro.kernels.config.KernelConfig`,
    normally derived at ``Mapper.lower`` time) fixes the tile geometry
    baked into the compiled sweep and, for matrix-form topologies with a
    ``dist_dtype``, stores the distance table in its lossless int8/int16
    packing — results bit-identical, gather bandwidth 4–8× lower.

    Every refine call records four tracer spans (:mod:`repro.obs.trace`):
    ``engine.upload`` (device graph, pairs and toggles, with the upload
    caches' hits), ``engine.dispatch``, ``engine.wait`` (until the
    outputs are ready on the device) and ``engine.readback`` (the
    transfers and the host float64 objective).
    """

    def __init__(self, topology, max_sweeps: int = 64,
                 eps_rel: float = _EPS_REL, use_pallas: bool | None = None,
                 interpret: bool | None = None,
                 cache_caps: dict | None = None,
                 kernel_config=None):
        import jax
        import jax.numpy as jnp
        kp = topology.kernel_params()
        self.topology = topology
        self.kind = kp[0]
        self.max_sweeps = int(max_sweeps)
        self.eps_rel = float(eps_rel)
        self.kernel_config = kernel_config
        if use_pallas is None or interpret is None:
            # compiled Pallas kernels on TPU; on CPU the fused-jnp gain
            # path (the kernels would only run interpreted there)
            from ..runtime.device import pallas_interpret
            interp = pallas_interpret()
            use_pallas = not interp if use_pallas is None else use_pallas
            interpret = interp if interpret is None else interpret
        self.use_pallas = bool(use_pallas)
        self.interpret = interpret = bool(interpret)
        if self.kind == "matrix":
            params = ()
            dist_dtype = getattr(kernel_config, "dist_dtype", None)
            if dist_dtype is not None:
                from ..kernels.config import quantize_table
                packed, _ = quantize_table(topology.matrix(), dist_dtype)
                self._D = jnp.asarray(packed)
            else:
                self._D = jnp.asarray(topology.matrix(), jnp.float32)
        else:
            params = kp[1:]
            self._D = jnp.zeros((1, 1), jnp.float32)    # ignored dummy
        self.params = params
        fn = _make_refine(self.kind, params, self.max_sweeps,
                          use_pallas=self.use_pallas, interpret=interpret,
                          config=kernel_config)
        self._refine_fn = fn            # raw sweep fn (fn.traces counts
        self._refine = jax.jit(fn)      # retraces — the tabu-masking
        # regression check asserts toggling tenure/dlb adds none)
        self._vrefine = jax.jit(jax.vmap(
            fn, in_axes=(0, 0, 0, 0, 0, 0, 0, 0, None, 0, None, None,
                         None)))
        # lane axis: ONE graph shared across a portfolio's restart lanes
        # (in_axes=None for every graph/pair array — no per-lane copies)
        self._lrefine = jax.jit(jax.vmap(
            fn, in_axes=(None, None, None, None, None, None, None, 0,
                         None, 0, None, None, None)))
        # internal LRU caps: session-level `cache_caps` plumbing (Mapper
        # passes {"graphs": ..., "pairs": ...}); evictions surface in
        # cache_info()
        self._caps = {"graphs": 16, "pairs": 16}
        if cache_caps:
            unknown = sorted(set(cache_caps) - set(self._caps))
            if unknown:
                raise ValueError(f"unknown engine cache_caps keys "
                                 f"{unknown}; known: "
                                 f"{sorted(self._caps)}")
            self._caps.update({k: int(v) for k, v in cache_caps.items()})
        self._evictions = {"graphs": 0, "pairs": 0}
        self._hits = {"graphs": 0, "pairs": 0}      # for span attributes
        # device uploads keyed by full array content (LRU): graph ELL/edge
        # arrays and candidate-pair arrays — long-lived serve() sessions
        # re-map the same structures, and the pair arrays alone can reach
        # ~32 MB (max_pairs entries), so neither re-transfers per request
        self._dg_cache: "OrderedDict[tuple, DeviceGraph]" = OrderedDict()
        self._pair_cache: "OrderedDict[tuple, tuple]" = OrderedDict()
        # bucketed pair-length high-water marks, one per bucket shape:
        # under a ShapeBucket with dynamic P, never shrink the padded
        # pair shape below one already compiled for that (K, E) — mixed
        # candidate sets then reuse the existing executable instead of
        # recompiling.  Scoped per bucket because executables are
        # (K, E, P)-specialized anyway: engines are shared across a
        # session's plans, and one bucket's huge pair set must not
        # inflate every other bucket's padding (inert but not free).
        self._p_hwm: dict = {}

    # ------------------------------------------------------------- host glue
    def _lru_get(self, cache: OrderedDict, key: tuple, build, cap: str):
        """Bounded fetch-or-build against ``self._caps[cap]`` (the
        session-level ``cache_caps`` plumbing); drops surface as
        ``cache_info()[f"{cap[:-1]}_evictions"]``."""
        val = cache.get(key)
        if val is None:
            val = build()
            cache[key] = val
            if len(cache) > self._caps[cap]:
                cache.popitem(last=False)
                self._evictions[cap] += 1
        else:
            cache.move_to_end(key)
            self._hits[cap] += 1
        return val

    def cache_info(self) -> dict:
        """Device-upload cache accounting: live entry counts plus the
        evictions forced by the ``cache_caps`` bounds."""
        return {
            "graph_entries": len(self._dg_cache),
            "graph_evictions": self._evictions["graphs"],
            "pair_entries": len(self._pair_cache),
            "pair_evictions": self._evictions["pairs"],
        }

    def trace_count(self) -> int:
        """How many times the sweep fn has been (re)traced — the
        tabu-masking regression check asserts this stays flat when
        ``tabu_tenure``/``dlb`` toggle at runtime."""
        return self._refine_fn.traces

    def _device_graph(self, g: CommGraph, k: int | None = None,
                      e: int | None = None) -> DeviceGraph:
        """Cached device upload of a graph, optionally re-padded into a
        plan bucket's (K, E) — padding is inert, so only the executable
        shape changes, never the result."""
        key = (g.n, hash(g.xadj.tobytes()), hash(g.adjncy.tobytes()),
               hash(np.asarray(g.adjwgt).tobytes()), k, e)

        def build():
            dg = DeviceGraph.from_comm(g)
            if k is not None or e is not None:
                dg = dg.pad_to(k if k is not None else dg.max_deg,
                               e if e is not None else dg.eu.shape[0])
            return dg

        return self._lru_get(self._dg_cache, key, build, "graphs")

    def _device_pairs(self, pairs: np.ndarray, pad_to: int = 128) -> tuple:
        pairs = np.asarray(pairs)
        key = (pad_to, pairs.shape[0], hash(pairs.tobytes()))
        return self._lru_get(self._pair_cache, key,
                             lambda: device_pairs(pairs, pad_to=pad_to),
                             "pairs")

    def _bucket_p(self, bucket, n_pairs: int) -> int:
        key = (bucket.max_deg, bucket.num_edges, bucket.num_pairs,
               bucket.schedule)
        p = max(bucket.pair_pad(n_pairs), self._p_hwm.get(key, 0))
        self._p_hwm[key] = p
        return p

    def _eps(self, j0: float) -> float:
        return self.eps_rel * max(1.0, abs(j0))

    def _stats(self, g: CommGraph, perm: np.ndarray, j0: float,
               trace: np.ndarray, sweeps: int, swaps: int,
               n_pairs: int, telemetry=None) -> SearchStats:
        stats = SearchStats()
        stats.initial_objective = j0
        stats.final_objective = qap_objective(g, self.topology, perm)
        stats.swaps = int(swaps)
        # gain passes actually run: one per applied sweep, plus the final
        # pass that found no positive gain when the loop converged before
        # the budget — same accounting as parallel_sweep_search
        passes = int(sweeps) + (1 if int(sweeps) < self.max_sweeps else 0)
        stats.evaluated = passes * n_pairs
        stats.objective_trace = [float(x) for x in trace[:int(sweeps) + 1]]
        if telemetry is not None:
            from ..obs.telemetry import EngineTelemetry
            stats.telemetry = EngineTelemetry.from_device(telemetry, trace)
        return stats

    @staticmethod
    def _tel_slice(tel, i=None) -> dict:
        """Host numpy view of one device telemetry pytree (lane/batch
        index ``i`` under vmap) — rides the transfer the perm/trace
        outputs already paid."""
        return {k: np.asarray(v if i is None else v[i])
                for k, v in tel.items()}

    @staticmethod
    def _toggles(tabu_tenure: int, dlb: bool, telemetry: bool = False
                 ) -> tuple:
        """Runtime tabu/don't-look/telemetry scalars as jnp arrays —
        value changes never retrace the compiled executables (masking,
        not retracing)."""
        import jax.numpy as jnp
        with host_boundary("engine.toggles"):
            return jnp.int32(tabu_tenure), jnp.bool_(dlb), \
                jnp.bool_(telemetry)

    def _upload(self, g: CommGraph, pairs: np.ndarray, bucket) -> tuple:
        """One graph and its candidate pairs on the device, padded into
        ``bucket``'s shapes when given: ``(DeviceGraph, us, vs)``."""
        if bucket is None:
            return (self._device_graph(g),) + self._device_pairs(pairs)
        dg = self._device_graph(g, k=bucket.max_deg, e=bucket.num_edges)
        return (dg,) + self._device_pairs(
            pairs, pad_to=self._bucket_p(bucket, len(pairs)))

    @contextmanager
    def _upload_span(self):
        """The ``engine.upload`` span, with the graph and pair upload
        cache hits inside it as attributes."""
        hits = dict(self._hits)
        with _TR.span("engine.upload") as sp:
            yield
            sp.attrs["graph_hits"] = self._hits["graphs"] - hits["graphs"]
            sp.attrs["pair_hits"] = self._hits["pairs"] - hits["pairs"]

    # ------------------------------------------------------------------ API
    def refine(self, g: CommGraph, perm: np.ndarray, pairs: np.ndarray,
               j0: float | None = None, bucket=None,
               tabu_tenure: int = 0, dlb: bool = False,
               telemetry: bool = False) -> SearchStats:
        """Refine ``perm`` in place over the candidate ``pairs`` — the
        device counterpart of ``parallel_sweep_search`` (one device
        dispatch, no host syncs until convergence).  ``j0`` is the
        caller's already-computed objective of ``perm`` (used for eps
        scaling and the reported initial objective); omitted, it is
        recomputed on host.  ``bucket`` (a
        :class:`~repro.core.spec.ShapeBucket`) pads the device arrays to
        the plan's fixed shapes so every same-bucket request reuses one
        compiled executable — inert, results unchanged.
        ``tabu_tenure``/``dlb`` enable the tabu memory and don't-look
        bits (see :func:`_make_refine`) — runtime toggles sharing the one
        executable; the defaults are bit-for-bit the pre-tabu sweep.
        ``telemetry`` enables the engine counter carries (same runtime
        discipline) and attaches an
        :class:`~repro.obs.telemetry.EngineTelemetry` to the stats."""
        import jax
        import jax.numpy as jnp
        if j0 is None:
            j0 = qap_objective(g, self.topology, perm)
        if len(pairs) == 0:
            stats = SearchStats()
            stats.initial_objective = stats.final_objective = j0
            stats.objective_trace = [j0]
            if telemetry:
                from ..obs.telemetry import EngineTelemetry
                stats.telemetry = EngineTelemetry(
                    objective_trace=np.asarray([j0]))
            return stats
        with self._upload_span():
            dg, us, vs = self._upload(g, pairs, bucket)
            tenure, dlb_, tel_ = self._toggles(tabu_tenure, dlb, telemetry)
        with host_boundary("engine.dispatch", span=True):
            dev_out = self._refine(
                dg.nbr, dg.wgt, dg.eu, dg.ev, dg.ew, us, vs,
                jnp.asarray(perm, jnp.int32), self._D,
                jnp.float32(self._eps(j0)), tenure, dlb_, tel_)
        with host_boundary("engine.wait", span=True):
            out_perm, trace, sweeps, swaps, tel = jax.block_until_ready(
                dev_out)
        with host_boundary("engine.readback", span=True):
            perm[:] = np.asarray(out_perm, dtype=perm.dtype)
            return self._stats(g, perm, j0, np.asarray(trace),
                               int(sweeps), int(swaps), len(pairs),
                               telemetry=self._tel_slice(tel)
                               if telemetry else None)

    def refine_batch(self, graphs, perms, pairs_list,
                     j0s=None, bucket=None, tabu_tenure: int = 0,
                     dlb: bool = False,
                     telemetry: bool = False) -> list[SearchStats]:
        """One vmapped device call over a batch of same-shape graphs.

        Per-graph arrays are padded to the batch's common (K, E, P)
        maxima — or, given a ``bucket``, to the plan's fixed shapes —
        inert by the DeviceGraph/pair padding invariants, so each result
        matches the corresponding single :meth:`refine`.  ``j0s`` are the
        callers' already-computed initial objectives (recomputed on host
        when omitted).
        """
        import jax
        import jax.numpy as jnp
        graphs = list(graphs)
        if not graphs:
            return []
        if j0s is None:
            j0s = [qap_objective(g, self.topology, p)
                   for g, p in zip(graphs, perms)]
        p_raw = max(max((len(p) for p in pairs_list), default=1), 1)
        with self._upload_span():
            if bucket is not None:
                k_max, e_max = bucket.max_deg, bucket.num_edges
                p_max = self._bucket_p(bucket, p_raw)
                dgs = [self._device_graph(g, k=k_max, e=e_max)
                       for g in graphs]
            else:
                dgs = [self._device_graph(g) for g in graphs]
                k_max = max(dg.max_deg for dg in dgs)
                e_max = max(dg.eu.shape[0] for dg in dgs)
                p_max = -(-p_raw // 128) * 128  # same bucketing as refine()
                dgs = [dg.pad_to(k_max, e_max) for dg in dgs]
            dev_pairs = [self._device_pairs(p, pad_to=p_max)
                         for p in pairs_list]
            tenure, dlb_, tel_ = self._toggles(tabu_tenure, dlb, telemetry)
        stack = lambda xs: jnp.stack(xs)                      # noqa: E731
        with host_boundary("engine.dispatch", span=True):
            dev_out = self._vrefine(
                stack([dg.nbr for dg in dgs]),
                stack([dg.wgt for dg in dgs]),
                stack([dg.eu for dg in dgs]),
                stack([dg.ev for dg in dgs]),
                stack([dg.ew for dg in dgs]),
                stack([u for u, _ in dev_pairs]),
                stack([v for _, v in dev_pairs]),
                stack([jnp.asarray(p, jnp.int32) for p in perms]),
                self._D,
                jnp.asarray([self._eps(j) for j in j0s], jnp.float32),
                tenure, dlb_, tel_)
        with host_boundary("engine.wait", span=True):
            out_perm, trace, sweeps, swaps, tel = jax.block_until_ready(
                dev_out)
        out = []
        with host_boundary("engine.readback", span=True):
            for i, (g, perm) in enumerate(zip(graphs, perms)):
                perm[:] = np.asarray(out_perm[i], dtype=perm.dtype)
                out.append(self._stats(
                    g, perm, j0s[i], np.asarray(trace[i]),
                    int(sweeps[i]), int(swaps[i]), len(pairs_list[i]),
                    telemetry=self._tel_slice(tel, i)
                    if telemetry else None))
        return out

    def refine_lanes(self, g: CommGraph, perms, pairs: np.ndarray,
                     j0s=None, bucket=None, tabu_tenure: int = 0,
                     dlb: bool = False,
                     telemetry: bool = False) -> list[SearchStats]:
        """One vmapped device call over L restart *lanes* of ONE graph —
        the portfolio counterpart of :meth:`refine_batch`: the graph and
        candidate-pair arrays are shared across lanes (``in_axes=None``,
        no per-lane copies), only the permutations and eps thresholds
        carry a lane axis.  Each lane's result equals a single
        :meth:`refine` of that lane's permutation (tested)."""
        import jax
        import jax.numpy as jnp
        perms = list(perms)
        if not perms:
            return []
        if j0s is None:
            j0s = [qap_objective(g, self.topology, p) for p in perms]
        if len(pairs) == 0:
            out = []
            for perm, j0 in zip(perms, j0s):
                stats = SearchStats()
                stats.initial_objective = stats.final_objective = j0
                stats.objective_trace = [j0]
                out.append(stats)
            return out
        with self._upload_span():
            dg, us, vs = self._upload(g, pairs, bucket)
            tenure, dlb_, tel_ = self._toggles(tabu_tenure, dlb, telemetry)
        with host_boundary("engine.dispatch", span=True):
            dev_out = self._lrefine(
                dg.nbr, dg.wgt, dg.eu, dg.ev, dg.ew, us, vs,
                jnp.stack([jnp.asarray(p, jnp.int32) for p in perms]),
                self._D,
                jnp.asarray([self._eps(j) for j in j0s], jnp.float32),
                tenure, dlb_, tel_)
        with host_boundary("engine.wait", span=True):
            out_perm, trace, sweeps, swaps, tel = jax.block_until_ready(
                dev_out)
        out = []
        with host_boundary("engine.readback", span=True):
            for i, perm in enumerate(perms):
                perm[:] = np.asarray(out_perm[i], dtype=perm.dtype)
                out.append(self._stats(
                    g, perm, j0s[i], np.asarray(trace[i]),
                    int(sweeps[i]), int(swaps[i]), len(pairs),
                    telemetry=self._tel_slice(tel, i)
                    if telemetry else None))
        return out


def refine(machine, g: CommGraph, perm: np.ndarray, pairs: np.ndarray,
           max_sweeps: int = 64, **kw) -> EngineResult:
    """One-shot convenience: build a :class:`RefinementEngine` over
    ``machine`` (Hierarchy or any Topology) and refine ``perm`` in place.
    Sessions should hold a ``Mapper`` (which caches engines) instead."""
    from ..topology.base import as_topology
    eng = RefinementEngine(as_topology(machine), max_sweeps=max_sweeps, **kw)
    stats = eng.refine(g, perm, pairs)
    return EngineResult(perm=perm, stats=stats,
                        sweeps=max(len(stats.objective_trace) - 1, 0))
