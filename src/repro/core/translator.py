"""Light-weight translator (paper §V): DSL program × schedule → executable.

The paper's argument: for a fixed domain you don't need a general-purpose
HLS compiler — a *light-weight* translator that (1) pattern-matches DSL
operators onto a library of pre-optimized hardware modules, (2) applies the
runtime scheduler's ``pipelines × PEs`` plan, and (3) lets the communication
manager place data, produces better code in far less time.

Since the IR refactor the translator is the *last* of three stages:

1. **front-end lowering** — :func:`repro.core.ir.lower_program` turns the
   :class:`~repro.core.dsl.VertexProgram` into a typed
   :class:`~repro.core.ir.SuperstepIR` op list;
2. **pass pipeline** — :func:`repro.core.passes.default_pipeline` runs the
   analysis/transform passes (module matching, identity folding, backend
   selection, gather+reduce fusion, dead-frontier elimination), each
   recording a before/after dump;
3. **translation** — :func:`translate` (this module) walks the optimized IR
   and emits the jitted superstep plus the
   :class:`TranslationReport`.

The TPU mapping implemented here:

* **Module matching** — the program's ``gather`` callable is classified
  against the static module menu (``kernels.ref.GATHER_OPS``) by abstract
  probing (no general syntax analysis — the paper's "eliminate complex
  grammatical and semantic analysis"). Unmatched gathers fall back to the
  general sparse jnp path, so nothing is rejected, only de-optimized.
* **Module library** — dense ELL Pallas kernel / dense XLA / sparse
  segment-reduce, selected by the scheduler heuristic (graph shape) and the
  backend hint.
* **Schedule application** — ``pipelines`` → edge-chunk streaming
  (``lax.scan``); ``PEs`` → ``shard_map`` edge partitions with
  psum/pmin/pmax combines (optionally int8-quantized by the comm manager).
* **Direction optimization** — when the direction-legality pass proved the
  push (scatter-over-out-edges) form equivalent, the translator emits *both*
  supersteps and stages a device-side ``lax.cond`` switch inside
  :meth:`CompiledGraphProgram.run`'s while_loop, keyed on frontier occupancy
  vs the scheduler's :class:`~repro.core.scheduler.DirectionPolicy`
  thresholds (Beamer-style alpha/beta).  Pull reads the transposed CSR
  (``G.reverse``); the push superstep is the frontier-compacted forward-ELL
  engine (``kernels/push_ell.py``): live rows compact into a capacity tier
  picked per superstep from the live row count, and frontiers wider than
  the largest tier fall back to the dense masked sweep, so push never
  costs meaningfully more than pull.
* **Multi-PE push** — under a ``pes > 1`` plan the push plane shards: the
  forward ELL splits into per-PE contiguous, degree-balanced row
  intervals (:func:`~repro.core.graph.shard_forward_ell`), each PE runs
  interval-local compaction + gather + segment-combine inside
  ``shard_map``, and the disjoint partial vertex tables combine with the
  reduce-matched collective — with the exchange routed through the
  :class:`~repro.core.comm.CommManager` so the run loop records executed
  transfer stats.  ``message_dtype='int8'`` additionally quantizes
  *float-add* pull-plane exchanges (the pagerank path) to an int8 wire
  format (:meth:`CommManager.quantized_psum`); min/max and integer
  reduces always keep the exact collective, so bfs/sssp/wcc stay
  bit-exact at any PE count.
* **Preprocessing cache** — every graph-derived layout (transposed CSR,
  degree buckets, forward ELL, COO) is memoized per graph in
  :mod:`repro.core.preprocess`, and the emitted/AOT-compiled supersteps
  are memoized per (program, graph, schedule), so a repeat ``translate``
  on the same inputs costs milliseconds.  ``TranslationReport.
  translate_breakdown`` itemizes preprocess vs passes vs AOT time.
* **AOT staging** — the translator compiles the superstep(s) eagerly and
  reports translation time (the paper's "TT" column) and cost estimates.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import time
import typing
import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops as kops
from ..kernels import pull_bitmap as pull_bitmap_kernel
from ..kernels import push_ell as push_ell_kernel
from ..kernels import push_scatter as push_kernel
from ..kernels.ref import ROW_REDUCE
from ..errors import (CheckpointCorruptError, CheckpointMismatchError,
                      DiagnosticError)
from . import checkpoint as ckpt
from . import faults
from . import graph as G
from . import preprocess
from ._jax_compat import shard_map_unchecked
from .analysis import analyze_program
from .comm import CommManager
from .diagnostics import max_severity, render_table
from .dsl import VertexProgram
from .ir import (ApplyOp, ExchangeOp, FrontierUpdateOp, FusedGatherReduceOp,
                 FusedSuperstepOp, PushScatterOp, SuperstepIR, lower_program)
from .passes import PassContext, classify_gather, default_pipeline
from .scheduler import (DirectionPolicy, ScheduleConfig, SchedulePlan, plan,
                        pull_block_capacities, push_capacity_tiers)

__all__ = ["classify_gather", "TranslationReport", "CompiledGraphProgram",
           "BatchLaneState", "translate"]

P = jax.sharding.PartitionSpec

# IR collective name (resolved by the backend-selection pass) → primitive;
# shared by the pull-plane exchange and the sharded push emitter
_COLLECTIVES = {"psum": jax.lax.psum, "pmin": jax.lax.pmin,
                "pmax": jax.lax.pmax}


# ---------------------------------------------------------------------------
# Compiled artifact
# ---------------------------------------------------------------------------


class BatchLaneState(typing.NamedTuple):
    """Resumable per-lane state of a batched run — the continuation hook.

    Mirrors the staged while-loop's carry exactly (values, frontier,
    iteration counter, the direction register, and every stats counter),
    with a leading lane axis on each field, so a run can stop after any
    superstep and resume bit-exactly: the direction register and the
    measured pull-cost register are part of the state, so an ``'auto'``
    lane resumed mid-run makes the same per-superstep direction choices a
    sequential :meth:`CompiledGraphProgram.run` would have made.

    Produced by :meth:`CompiledGraphProgram.batch_init`, advanced by
    :meth:`CompiledGraphProgram.run_batch_slice`, recycled lane-by-lane by
    :meth:`CompiledGraphProgram.lane_admit` — the primitive the serving
    plane (:mod:`repro.serve.graph_serve`) builds continuous batching on:
    converged lanes free their slots for waiting queries without
    restarting (or perturbing) the still-running lanes.
    """

    values: jax.Array        # (k, V) per-lane vertex tables
    active: jax.Array        # (k, V) bool frontiers
    iters: jax.Array         # (k,) supersteps executed per lane
    direction: jax.Array     # (k,) direction register (0=pull, 1=push)
    pushes: jax.Array        # (k,) push supersteps
    compact: jax.Array       # (k,) compacted push supersteps
    switches: jax.Array      # (k,) direction switches
    pe_hi: jax.Array         # (k,) push edge counter, high 16-bit words
    pe_lo: jax.Array         # (k,) push edge counter, low words
    pe_rows: jax.Array       # (k, pes) live fwd-ELL rows per PE
    pl_hi: jax.Array         # (k,) pull swept-edge counter, high words
    pl_lo: jax.Array         # (k,) pull swept-edge counter, low words
    bl_swept: jax.Array      # (k,) pull blocks swept (bitmap plane)
    bl_skip: jax.Array       # (k,) pull blocks skipped
    pull_cost: jax.Array     # (k,) measured pull-cost register


@dataclasses.dataclass
class TranslationReport:
    program: str
    backend: str                  # 'dense_pallas' | 'dense_xla' | 'sparse_xla'
    gather_module: str | None     # matched menu entry, None → general path
    reduce_module: str
    pipelines: int
    pes: int
    translate_time_s: float
    est_flops_per_superstep: float
    est_bytes_per_superstep: float
    est_collective_bytes: int
    dsl_lines: int | None = None  # set by callers for Table V
    # typed lint/verifier findings accumulated on PassContext during the
    # pass pipeline (repro.core.diagnostics.Diagnostic tuple) — the
    # structured successor to grepping SuperstepIR.notes
    diagnostics: tuple = ()
    pass_report: str | None = None  # per-pass dump (translate(dump_passes=True))
    ir_dump: str | None = None      # final optimized IR listing
    direction_policy: str | None = None  # e.g. "auto(alpha=1, beta=4)"
    directions: tuple = ("pull",)   # supersteps emitted: ('pull',[ 'push'])
    run_stats: dict | None = None   # last run's direction stats (see run())
    # translate-time itemization: preprocess_s (graph layouts built this
    # call), passes_s, emit_s, aot_s, total_s, staging_cached (True when
    # the emitted+compiled supersteps came from the staging cache),
    # preprocess_cached (True when every layout came from the graph-keyed
    # preprocessing cache — preprocess_s is then 0.0 *because nothing was
    # built*, not because building was instant)
    translate_breakdown: dict | None = None
    push_layout: str | None = None  # 'fwd_ell' | 'coo_chunks' (push emitted)
    push_tiers: tuple | None = None  # compaction row capacities (fwd_ell)
    # chunk geometry actually staged by the sparse module — always equal to
    # (plan.num_chunks, plan.chunk_size) since the plan owns the rounding
    staged_chunks: tuple | None = None
    exchange_plane: str | None = None   # 'pull' | 'push' | None (un-sharded)
    exchange_quantized: bool = False    # int8 wire format on the exchange
    push_pe_rows: tuple | None = None   # per-PE forward-ELL interval rows
    push_pe_edges: tuple | None = None  # per-PE edge counts (balance stats)
    pull_sweep: str = "dense"           # pull plane: 'bitmap' | 'dense'
    pull_block_tiers: tuple | None = None  # total live-block caps per tier
    pull_blocks_total: int | None = None   # skippable blocks (bitmap plane)
    est_frontier_bytes: int = 0         # mask-exchange bytes per superstep
    # out-of-core partition stream (repro.core.stream): interval count and
    # the store's byte budget; 1/None on resident translations
    num_partitions: int = 1
    partition_budget_bytes: int | None = None


class CompiledGraphProgram:
    """The translated executable (paper: generated HDL + host C code).

    When translation emitted both directions, :meth:`run`'s while_loop
    carries a direction register and switches push ⇄ pull per superstep
    with a staged ``lax.cond``, following the scheduler's
    :class:`~repro.core.scheduler.DirectionPolicy` (alpha/beta hysteresis
    on frontier occupancy).  Both directions compute the identical
    superstep function, so results are bit-exact regardless of the policy.
    """

    def __init__(self, superstep, init_state, report: TranslationReport,
                 max_iters: int, *, layouts: dict,
                 push_superstep=None,
                 direction: DirectionPolicy | None = None,
                 num_vertices: int = 0, num_edges: int = 0,
                 push_tiers: tuple | None = None,
                 loop_cache: dict | None = None, push_rf_fn=None,
                 push_stat_pes: int = 1, comm: CommManager | None = None,
                 exchange_plane: str | None = None,
                 collective_bytes_per_superstep: int = 0,
                 probe_divergence: bool = False,
                 checkpoint_dir: str | None = None,
                 checkpoint_every: int | None = None,
                 fingerprints_fn=None):
        # every staged function takes the placed layout arrays first —
        # ``(layouts, values, active)`` — so no jitted program closes over
        # graph data (a closed-over array lowers to an HLO constant)
        self._layouts = layouts
        self._superstep = superstep
        self._push_superstep = push_superstep
        self._init_state = init_state
        self._direction = direction or DirectionPolicy(mode="pull")
        self._mode = self._direction.mode if push_superstep is not None \
            else "pull"
        # shared across staging-cache siblings: the jitted while-loops are
        # keyed per mode and identical for every translate of the same
        # (program, graph, schedule), so reuse avoids re-tracing
        self._loop_cache: dict = loop_cache if loop_cache is not None else {}
        self._push_tiers = push_tiers             # (small, large), or None
        # live forward-ELL rows per PE: (layouts, active) -> (push_stat_pes,)
        # int32 — the sharded engine's per-PE interval counter, or the
        # single-PE forward ELL's scalar form; None without a fwd_ell push
        self._push_rf_fn = push_rf_fn
        self._push_stat_pes = push_stat_pes       # rf vector length (static)
        self._comm = comm
        self._exchange_plane = exchange_plane     # 'pull' | 'push' | None
        self._collective_bytes = int(collective_bytes_per_superstep)
        self._num_vertices = num_vertices
        self._num_edges = num_edges
        self.report = report
        self.max_iters = max_iters
        # opt-in NaN probe (ScheduleConfig.probe_divergence): a superstep
        # producing NaN freezes the frontier so the loop exits and the run
        # reports terminated='diverged'.  NaN only — +inf is a legitimate
        # min-reduce identity (SSSP's unreached vertices), not divergence.
        self._probe = bool(probe_divergence)
        # durable checkpointing (core/checkpoint.py): translate-time
        # defaults for run(checkpoint_dir=...); the fingerprint closure is
        # lazy so un-checkpointed runs never pay the graph-bytes CRC
        self._checkpoint_dir = checkpoint_dir
        self._checkpoint_every = checkpoint_every
        self._fingerprints_fn = fingerprints_fn
        self._fingerprints_cache: dict | None = None
        self.last_run_stats: dict | None = None

    def _fingerprints(self) -> dict:
        if self._fingerprints_cache is None:
            if self._fingerprints_fn is None:
                raise ValueError(
                    "this program was constructed without fingerprint "
                    "inputs; checkpointing needs translate()")
            self._fingerprints_cache = self._fingerprints_fn()
        return self._fingerprints_cache

    @property
    def layouts(self) -> dict:
        """The placed graph layouts: the first argument of every staged
        function (a pytree of device arrays, placed once at translate)."""
        return self._layouts

    def init_state(self, roots=None, values=None):
        return self._init_state(self._layouts, roots=roots, values=values)

    def superstep(self, values, active):
        """One pull-direction superstep (the canonical form).

        The staged pull superstep internally also returns its sweep stats
        (swept edges, swept/skipped blocks — see :meth:`run`); this public
        form keeps the ``(values, active)`` contract.
        """
        new, nxt, _ = self._superstep(self._layouts, values, active)
        return new, nxt

    def superstep_push(self, values, active):
        """One push-direction superstep; ``None``-guard via report.directions."""
        if self._push_superstep is None:
            raise ValueError("program was translated pull-only "
                             f"({self.report.direction_policy})")
        return self._push_superstep(self._layouts, values, active)

    @property
    def run_loop(self):
        """The jitted run-to-convergence loop for this program's direction
        mode: a pure function of ``(layouts, values, active)`` (see
        :meth:`_staged_loop`), lowerable from shapes alone."""
        return self._staged_loop(self._mode)

    def _staged_loop(self, mode: str):
        """Algorithm 1's while-loop with the direction register, jitted.

        Staged once per (program, mode) — an eager ``lax.while_loop``
        would re-trace on every :meth:`run` call — and pure (vmap-safe):
        per-lane freeze guards let :meth:`run_batch` vmap it without
        over-counting iterations on converged lanes.  The jitted function
        maps ``(layouts, values, active)`` to ``(values, iters, (push_steps,
        compacted_push_steps, switches, push_edges_hi, push_edges_lo,
        push_live_rows, pull_edges_hi, pull_edges_lo, pull_blocks_swept,
        pull_blocks_skipped, pull_cost))`` — both edge counters are split
        into 16-bit words so their sums never overflow int32 (callers
        recombine with python ints); ``push_live_rows`` accumulates the
        live forward-ELL row counts per PE across push supersteps (a
        ``(pes,)`` vector under the sharded engine, ``(1,)`` otherwise);
        the pull counters accumulate the bitmap plane's *swept* cost
        (edges in swept blocks — E on dense full sweeps) and block
        skip/sweep split; ``pull_cost`` is the measured-pull-cost
        register the ``'auto'`` policy's m_f-aware alpha reads.

        Frontier occupancy (``m_f``, ``n_f``) is computed on the
        replicated frontier — numerically identical to psum-ing each PE's
        partial count, since the per-PE edge intervals partition the edge
        set — so the direction register agrees on every PE by
        construction and the staged ``lax.cond`` never diverges across
        the mesh.
        """
        if mode in self._loop_cache:
            return self._loop_cache[mode]
        cond, body = self._loop_fns(mode)
        E = self._num_edges
        n_pe = self._push_stat_pes
        probe = self._probe

        @jax.jit
        def loop(layouts, values, active):
            z = jnp.asarray(0, jnp.int32)
            state = (values, active, z, z, z, z, z, z, z,
                     jnp.zeros((n_pe,), jnp.int32), z, z, z, z,
                     jnp.asarray(E, jnp.int32))
            values, active, iters, _, pushes, compact, switches, \
                pe_hi, pe_lo, pe_rows, pl_hi, pl_lo, bl_swept, bl_skip, \
                pull_cost = jax.lax.while_loop(
                    cond, functools.partial(body, layouts), state)
            # exit diagnostics for run_stats['terminated']: was the
            # frontier still live (budget hit) and — probe only — did the
            # values table pick up a NaN (divergence)?
            live = jnp.any(active)
            if probe and jnp.issubdtype(values.dtype, jnp.floating):
                nanfree = ~jnp.any(jnp.isnan(values))
            else:
                nanfree = jnp.asarray(True)
            return values, iters, (pushes, compact, switches, pe_hi, pe_lo,
                                   pe_rows, pl_hi, pl_lo, bl_swept, bl_skip,
                                   pull_cost, live, nanfree)

        self._loop_cache[mode] = loop
        return loop

    def _loop_fns(self, mode: str):
        """The staged loop's ``(cond, body)`` over the 15-field lane state.

        Shared by :meth:`_staged_loop` (run-to-convergence) and the
        budgeted slice loop behind :meth:`run_batch_slice` — one body
        means a sliced run steps through the *identical* superstep
        sequence a monolithic run would, so continuation is bit-exact by
        construction.  The state tuple field order is exactly
        :class:`BatchLaneState` (without the lane axis).  ``body`` takes
        the placed layouts first: ``body(layouts, state)``.
        """
        pull, push = self._superstep, self._push_superstep
        policy = self._direction
        V, E = self._num_vertices, self._num_edges
        rf_fn = self._push_rf_fn
        n_pe = self._push_stat_pes
        tiers = self._push_tiers
        max_iters = self.max_iters
        probe = self._probe

        def choose(layouts, prev_dir, active, pull_cost):
            # frontier occupancy: n_f vertices, m_f out-edges (≤ E < 2^31)
            m_f = jnp.sum(jnp.where(active, layouts["deg"], 0))
            if mode == "pull":
                return jnp.asarray(0, jnp.int32), m_f
            if mode == "push":
                return jnp.asarray(1, jnp.int32), m_f
            n_f = jnp.sum(active.astype(jnp.int32))
            # m_f-aware alpha: compare push work against the *measured*
            # pull cost (the last pull superstep's swept edges — the
            # bitmap plane sweeps far less than E on narrow frontiers),
            # not a full-E sweep the pull plane may never pay
            stay_push = m_f.astype(jnp.float32) * policy.alpha \
                < pull_cost.astype(jnp.float32)
            enter_push = n_f.astype(jnp.float32) * policy.beta < V
            return (jnp.where(prev_dir == 1, stay_push, enter_push)
                    .astype(jnp.int32), m_f)

        def live_rows(layouts, active):
            # live forward-ELL rows per PE, the quantity the push
            # superstep's tier/fallback guard switches on (recomputed
            # there: the superstep's public (values, active) signature
            # stays, and the O(R) reduce is noise next to the superstep)
            if mode == "pull" or rf_fn is None:
                return jnp.zeros((n_pe,), jnp.int32)
            return rf_fn(layouts, active)

        def compacted(direction, rf):
            # did this push superstep fit a compaction tier (vs the dense
            # fallback)?
            if mode == "pull" or rf_fn is None or tiers is None:
                return direction        # pull: always 0; coo_chunks:
                                        # chunk-skip counts as compaction
            return direction * (jnp.max(rf) <= tiers[-1]).astype(jnp.int32)

        zero_pstats = tuple(jnp.asarray(0, jnp.int32) for _ in range(3))

        def step(layouts, direction, values, active):
            # every branch returns (values, active, (swept_edges,
            # blocks_swept, blocks_skipped)) — push supersteps have no
            # pull-plane sweep, so their stats are zeros
            if mode == "pull":
                return pull(layouts, values, active)
            if mode == "push":
                return (*push(layouts, values, active), zero_pstats)
            return jax.lax.cond(
                direction == 1,
                lambda v, a: (*push(layouts, v, a), zero_pstats),
                lambda v, a: pull(layouts, v, a), values, active)

        def cond(state):
            _, active, it, *_ = state
            return jnp.logical_and(jnp.any(active), it < max_iters)

        def body(layouts, state):
            values, active, it, direction, pushes, compact, switches, \
                pe_hi, pe_lo, pe_rows, pl_hi, pl_lo, bl_swept, bl_skip, \
                pull_cost = state
            alive = jnp.logical_and(jnp.any(active), it < max_iters)
            new_dir, m_f = choose(layouts, direction, active, pull_cost)
            rf = live_rows(layouts, active)
            new_values, new_active, pstats = step(layouts, new_dir, values,
                                                  active)
            if probe and jnp.issubdtype(new_values.dtype, jnp.floating):
                # divergence probe: a NaN anywhere in the new table
                # freezes the frontier, so the loop exits on the next
                # cond and run_stats reads terminated='diverged'.  The
                # poisoned values are kept — they are the evidence.
                new_active = jnp.logical_and(
                    new_active, ~jnp.any(jnp.isnan(new_values)))
            inc = alive.astype(jnp.int32)
            values = jnp.where(alive, new_values, values)
            pushes = pushes + new_dir * inc
            compact = compact + compacted(new_dir, rf) * inc
            pe_rows = pe_rows + rf * new_dir * inc
            active = jnp.where(alive, new_active, active)
            switches = switches + (new_dir != direction).astype(jnp.int32) * inc
            # push and pull cost counters are both device-side now: m_f
            # per push superstep, *swept* edges per pull superstep (the
            # bitmap plane's real cost — E only on dense full sweeps).
            # Both fit int32 (≤ E) but their sums may not, so accumulate
            # split 16-bit words (exact up to ~32k supersteps × full
            # sweeps ≈ 2^47 edges); run() recombines with python ints.
            m_f = m_f.astype(jnp.int32)
            pe_hi = pe_hi + (m_f >> 16) * new_dir * inc
            pe_lo = pe_lo + (m_f & 0xFFFF) * new_dir * inc
            swept_e, swept_b, skip_b = pstats
            pull_inc = (1 - new_dir) * inc
            pl_hi = pl_hi + (swept_e >> 16) * pull_inc
            pl_lo = pl_lo + (swept_e & 0xFFFF) * pull_inc
            bl_swept = bl_swept + swept_b * pull_inc
            bl_skip = bl_skip + skip_b * pull_inc
            # measured pull-cost register for the m_f-aware direction
            # choice: what the pull plane actually swept last time
            pull_cost = jnp.where(pull_inc == 1, swept_e, pull_cost)
            direction = jnp.where(alive, new_dir, direction)
            return values, active, it + inc, direction, pushes, compact, \
                switches, pe_hi, pe_lo, pe_rows, pl_hi, pl_lo, bl_swept, \
                bl_skip, pull_cost

        return cond, body

    def _scalar_stats(self, iters, pushes, compact, switches, pe_hi, pe_lo,
                      pe_rows, pl_hi, pl_lo, bl_swept, bl_skip, pull_cost,
                      live, nanfree) -> dict:
        """run()'s stats dict from host-side scalar counters.

        Shared by :meth:`run` (counters off the staged loop's exit) and
        the checkpointed slice driver (counters off the final
        :class:`BatchLaneState` — the slice carry holds the identical
        fields), so both paths report bit-identically.
        """
        if not bool(nanfree):
            terminated = "diverged"
        elif bool(live) and int(iters) >= self.max_iters:
            terminated = "budget"
        else:
            terminated = "converged"
        pull_steps = int(iters) - int(pushes)
        exchanges = {"pull": pull_steps, "push": int(compact)}.get(
            self._exchange_plane, 0)
        return {
            "push_supersteps": int(pushes),
            "push_compacted_supersteps": int(compact),
            "push_fallback_supersteps": int(pushes) - int(compact),
            "pull_supersteps": pull_steps,
            "direction_switches": int(switches),
            # exact: hi/lo-recombined pull part (swept edges — the real
            # pull cost model, ≤ pull_supersteps·E) + push part (m_f)
            "edges_traversed": (int(pl_hi) << 16) + int(pl_lo)
            + (int(pe_hi) << 16) + int(pe_lo),
            "pes": self.report.pes,
            "push_live_rows_per_pe": np.asarray(pe_rows).tolist(),
            # block-skip split of the bitmap pull plane (zeros on the
            # dense plane, which has no block accounting) — the pull-side
            # analogue of the push compacted/fallback tier split
            "pull_blocks_swept": int(bl_swept),
            "pull_blocks_skipped": int(bl_skip),
            # the measured-pull-cost register's final value (E until a
            # pull superstep runs) — what the m_f-aware alpha compared
            "pull_cost_model": int(pull_cost),
            "exchange_supersteps": exchanges,
            "exchange_bytes": exchanges * self._collective_bytes,
            # how the run ended: 'converged' (frontier drained),
            # 'budget' (superstep budget hit with a live frontier —
            # values are partial), or 'diverged' (NaN probe fired)
            "terminated": terminated,
        }

    def run(self, roots=None, values=None, *, checkpoint_dir=None,
            checkpoint_every=None, resume=False):
        """Paper Algorithm 1's while-loop, as a device-side while_loop.

        With both directions emitted and an ``'auto'`` policy, every
        superstep re-decides its direction on frontier occupancy (the
        runtime scheduler picking the right module per phase).  Per-run
        direction stats land on ``self.last_run_stats`` and
        ``report.run_stats``: push/pull superstep counts, direction
        switches, and the algorithmic edge-traversal count (``m_f`` per
        push superstep, ``E`` per pull superstep).  The compacted vs
        fallback split is meaningful for the ``fwd_ell`` push layout
        (which capacity tier ran); under ``coo_chunks`` every push
        superstep counts as compacted — chunk-granular ``lax.cond``
        skipping is that layout's compaction mechanism, it has no dense
        fallback (check ``report.push_layout`` when comparing engines).

        Under a multi-PE plan the stats additionally record the exchange
        plane's *executed* traffic — ``exchange_supersteps`` (supersteps
        that ran a cross-PE collective: every pull superstep on the
        sparse sharded plan, every compacted push superstep on the dense
        sharded-push plan — the fallback sweep is replicated and
        exchanges nothing) and ``exchange_bytes`` — and accumulate them
        on the translation-time :class:`~repro.core.comm.CommManager`
        (``comm.stats.collective_bytes_total``), so transfer reports
        reflect what ran, not just the static estimate.
        ``push_live_rows_per_pe`` sums each PE's live forward-ELL rows
        over the run's push supersteps (the per-PE load-balance view of
        the frontier; a single entry when the push engine is un-sharded).

        With ``checkpoint_dir=`` (here or at translate time) the run is
        driven through the budgeted slice loop instead, committing a
        durable snapshot every ``checkpoint_every`` supersteps
        (:data:`~repro.core.checkpoint.DEFAULT_LANE_SUPERSTEPS` by
        default); ``resume=True`` restores the newest snapshot —
        fingerprint-checked against this program/graph/schedule — and
        continues bit-exactly (one shared loop body means slices replay
        the identical superstep sequence).  ``run_stats`` gains
        ``checkpoint_saves``/``checkpoint_loads``/``checkpoint_write_s``.
        """
        if checkpoint_dir is None:
            checkpoint_dir = self._checkpoint_dir
        if checkpoint_dir is not None:
            return self._run_checkpointed(roots, values, checkpoint_dir,
                                          checkpoint_every, resume)
        values, active = self.init_state(roots=roots, values=values)
        values, iters, stats_dev = self.run_loop(self._layouts, values,
                                                 active)
        # one host transfer for the whole counter tuple (a per-scalar
        # int() would pay a device sync each)
        iters, (pushes, compact, switches, pe_hi, pe_lo, pe_rows,
                pl_hi, pl_lo, bl_swept, bl_skip, pull_cost, live,
                nanfree) = \
            jax.device_get((iters, stats_dev))
        stats = self._scalar_stats(iters, pushes, compact, switches, pe_hi,
                                   pe_lo, pe_rows, pl_hi, pl_lo, bl_swept,
                                   bl_skip, pull_cost, live, nanfree)
        if self._comm is not None and self._exchange_plane is not None:
            self._comm.stats.record_collective(
                self._collective_bytes, stats["exchange_supersteps"])
        self.last_run_stats = stats
        self.report.run_stats = stats
        return values, iters

    def _initial_lane_state(self, roots, values) -> BatchLaneState:
        """A 1-lane :class:`BatchLaneState` for run()'s request shapes."""
        if values is None and roots is not None:
            return self.batch_init(jnp.asarray([int(np.asarray(roots))]))
        v0, a0 = self.init_state(roots=roots, values=values)
        base = self.batch_idle(1)
        return base._replace(values=v0[None, :], active=a0[None, :])

    def _run_checkpointed(self, roots, values, directory, every, resume):
        """run() driven through budgeted slices with durable snapshots.

        Bit-exactness is by construction: the slice loop shares
        :meth:`_loop_fns`'s cond/body with the monolithic loop, and the
        snapshot carries the complete 15-field carry, so crash → restore
        → finish walks the identical superstep sequence.  Counters ride
        the carry, so the final ``run_stats`` merge across segments for
        free.  The ``lane.crash`` fault point trips at every slice
        boundary for the chaos harness.
        """
        every = int(every or self._checkpoint_every
                    or ckpt.DEFAULT_LANE_SUPERSTEPS)
        fps = self._fingerprints()
        root_meta = None if roots is None else int(np.asarray(roots))
        saves = loads = seq = 0
        write_s = 0.0
        state = None
        if resume:
            stem = ckpt.latest_snapshot(directory, "lane")
            if stem is not None:
                manifest, arrays = ckpt.read_snapshot(stem, kind="lane",
                                                      expect=fps)
                meta = manifest["meta"]
                if meta.get("root") != root_meta:
                    raise CheckpointMismatchError(
                        f"snapshot {stem} was rooted at "
                        f"{meta.get('root')!r}, this run requests "
                        f"{root_meta!r}", field="root",
                        expected=str(root_meta), got=str(meta.get("root")))
                state = self.lane_restore(arrays)
                seq = int(manifest["seq"]) + 1
                saves = int(meta.get("checkpoint_saves", 0))
                loads = 1
        if state is None:
            state = self._initial_lane_state(roots, values)
        while not bool(self.lane_done(state)[0]):
            faults.trip("lane.crash",
                        payload={"iters": int(np.asarray(state.iters)[0])})
            state = self.run_batch_slice(state, every)
            t0 = time.perf_counter()
            saves += 1
            ckpt.write_snapshot(directory, "lane", seq,
                                self.lane_snapshot(state),
                                {"root": root_meta,
                                 "checkpoint_saves": saves}, fps)
            write_s += time.perf_counter() - t0
            seq += 1
        host = jax.device_get(
            (state.iters, state.pushes, state.compact, state.switches,
             state.pe_hi, state.pe_lo, state.pe_rows, state.pl_hi,
             state.pl_lo, state.bl_swept, state.bl_skip, state.pull_cost,
             jnp.any(state.active, axis=1)))
        (iters, pushes, compact, switches, pe_hi, pe_lo, pe_rows, pl_hi,
         pl_lo, bl_swept, bl_skip, pull_cost, live) = \
            (np.asarray(a)[0] for a in host)
        if self._probe and jnp.issubdtype(state.values.dtype, jnp.floating):
            nanfree = not bool(jax.device_get(
                jnp.any(jnp.isnan(state.values[0]))))
        else:
            nanfree = True
        stats = self._scalar_stats(iters, pushes, compact, switches, pe_hi,
                                   pe_lo, pe_rows, pl_hi, pl_lo,
                                   bl_swept, bl_skip, pull_cost, live,
                                   nanfree)
        stats["checkpoint_saves"] = saves
        stats["checkpoint_loads"] = loads
        stats["checkpoint_write_s"] = write_s
        if self._comm is not None and self._exchange_plane is not None:
            self._comm.stats.record_collective(
                self._collective_bytes, stats["exchange_supersteps"])
        self.last_run_stats = stats
        self.report.run_stats = stats
        return state.values[0], int(iters)

    def run_batch(self, roots):
        """Batched Algorithm 1: vmap the while-loop over k root vertices.

        Returns ``(values (k, V), iters (k,))`` — each row identical to a
        sequential ``run(roots=root)``.  Converged lanes freeze (values,
        frontier, and iteration counter) while slower lanes finish, so the
        batch matches k sequential runs exactly.  Per-lane direction stats
        land on ``last_run_stats`` (lists, one entry per lane).

        Batched runs honor the direction policy, including per-lane
        ``'auto'`` switching: the compacted push kernel is data-indexed
        (cumsum compaction, no chunk ``lax.cond``), so it vmaps cleanly.
        The cost trade-off is explicit: under vmap both the direction
        ``cond`` and the tier ``switch`` lower to execute-all-branches
        selects, so each batched auto superstep pays the pull module,
        both compacted tiers, *and* the dense fallback (≈2× a pull-pinned
        batch, vs ~7× with the old O(E) chunk-scan push).  Pin
        ``DirectionPolicy(mode='pull')`` when batched throughput matters
        more than per-lane direction stats — results are bit-identical
        either way.

        Multi-PE programs batch too (the sharded push engine's
        ``shard_map`` is staged with the vma checker disabled — see
        :func:`repro.core._jax_compat.shard_map_unchecked` — because the
        checker cannot type vmapped shard_maps).  The stats' per-lane
        ``exchange_supersteps``/``exchange_bytes`` stay *logical* (the
        algorithmic cost model, matching sequential runs lane-for-lane),
        while the comm manager's executed totals record the *physical*
        batched traffic: under vmap the direction/tier conds become
        execute-both-branches selects and converged lanes step until the
        slowest lane finishes, so the exchange runs every batched
        superstep over every lane's table.
        """
        roots = jnp.asarray(roots)
        loop = self._staged_loop(self._mode)
        init_state = self._init_state

        def one(layouts, root):
            values, active = init_state(layouts, roots=root)
            return loop(layouts, values, active)

        values, iters, (pushes, compact, switches, pe_hi, pe_lo, pe_rows,
                        pl_hi, pl_lo, bl_swept, bl_skip, _, live,
                        nanfree) = \
            jax.vmap(one, in_axes=(None, 0))(self._layouts, roots)
        iters_np = np.asarray(iters)
        stats = self._batch_stats(iters, pushes, compact, switches, pe_hi,
                                  pe_lo, pe_rows, pl_hi, pl_lo, bl_swept,
                                  bl_skip, live=live, nanfree=nanfree)
        if self._comm is not None and self._exchange_plane is not None:
            # physical traffic: vmap lowers the direction/tier conds to
            # execute-both-branches selects and converged lanes keep
            # stepping until the slowest finishes, so the sharded
            # exchange runs every batched superstep over every lane's
            # table — that, not the logical per-lane sum, is what the
            # comm manager's executed totals must record
            self._comm.stats.record_collective(
                self._collective_bytes * int(roots.shape[0]),
                int(iters_np.max()) if iters_np.size else 0)
        self.last_run_stats = stats
        self.report.run_stats = stats
        return values, iters

    def _batch_stats(self, iters, pushes, compact, switches, pe_hi, pe_lo,
                     pe_rows, pl_hi, pl_lo, bl_swept, bl_skip, *,
                     live=None, nanfree=None) -> dict:
        """Per-lane stats lists from device counters (one host transfer).

        Shared by :meth:`run_batch` (counters straight off the vmapped
        loop) and :meth:`lane_stats` (counters off a
        :class:`BatchLaneState`) so both surfaces report identically.
        ``live``/``nanfree`` are the per-lane exit diagnostics (frontier
        still live / no NaN observed) behind the ``terminated`` list:
        'converged', 'budget', 'diverged', or 'running' (a sliced lane
        that has not finished its budgeted run yet).
        """
        (iters_np, pushes_np, compact_np, switches_np, pe_hi_np, pe_lo_np,
         pe_rows_np, pl_hi_np, pl_lo_np, bl_swept_np, bl_skip_np) = \
            (np.asarray(a) for a in jax.device_get(
                (iters, pushes, compact, switches, pe_hi, pe_lo, pe_rows,
                 pl_hi, pl_lo, bl_swept, bl_skip)))
        pulls_np = iters_np - pushes_np
        push_edges = (pe_hi_np.astype(np.int64) << 16) + pe_lo_np
        pull_edges = (pl_hi_np.astype(np.int64) << 16) + pl_lo_np
        exchanges_np = {"pull": pulls_np, "push": compact_np}.get(
            self._exchange_plane, np.zeros_like(pulls_np))
        k = int(iters_np.shape[0])
        live_np = (np.asarray(jax.device_get(live)) if live is not None
                   else np.zeros(k, bool))
        nanfree_np = (np.asarray(jax.device_get(nanfree))
                      if nanfree is not None else np.ones(k, bool))
        terminated = []
        for i in range(k):
            if not nanfree_np[i]:
                terminated.append("diverged")
            elif not live_np[i]:
                terminated.append("converged")
            elif int(iters_np[i]) >= self.max_iters:
                terminated.append("budget")
            else:
                terminated.append("running")
        return {
            "batch_size": int(iters_np.shape[0]),
            "push_supersteps": pushes_np.tolist(),
            "push_compacted_supersteps": compact_np.tolist(),
            "push_fallback_supersteps": (pushes_np - compact_np).tolist(),
            "pull_supersteps": pulls_np.tolist(),
            "direction_switches": switches_np.tolist(),
            "edges_traversed": (pull_edges + push_edges).tolist(),
            "pes": self.report.pes,
            "push_live_rows_per_pe": pe_rows_np.tolist(),
            "pull_blocks_swept": bl_swept_np.tolist(),
            "pull_blocks_skipped": bl_skip_np.tolist(),
            # per-lane *logical* counts (the algorithmic cost model);
            # physical accounting differs under vmap — see run_batch
            "exchange_supersteps": exchanges_np.tolist(),
            "exchange_bytes": (exchanges_np.astype(np.int64)
                               * self._collective_bytes).tolist(),
            "terminated": terminated,
        }

    # -- lane-level continuation: resumable batched runs (serving plane) ---

    def _fresh_lane(self, layouts, root) -> BatchLaneState:
        """One lane's freshly-rooted full loop state (no lane axis)."""
        values, active = self._init_state(layouts, roots=root)
        z = jnp.asarray(0, jnp.int32)
        return BatchLaneState(
            values=values, active=active, iters=z, direction=z, pushes=z,
            compact=z, switches=z, pe_hi=z, pe_lo=z,
            pe_rows=jnp.zeros((self._push_stat_pes,), jnp.int32),
            pl_hi=z, pl_lo=z, bl_swept=z, bl_skip=z,
            pull_cost=jnp.asarray(self._num_edges, jnp.int32))

    def batch_init(self, roots) -> BatchLaneState:
        """Root a k-lane :class:`BatchLaneState` without running any steps.

        Each lane carries the *complete* staged-loop carry, so slicing the
        batch with :meth:`run_batch_slice` replays the exact superstep
        sequence ``run``/``run_batch`` would execute — answers are
        bit-exact against the sequential oracle by construction.
        """
        key = ("batch_init",)
        fn = self._loop_cache.get(key)
        if fn is None:
            fn = jax.jit(jax.vmap(self._fresh_lane, in_axes=(None, 0)))
            self._loop_cache[key] = fn
        return fn(self._layouts, jnp.asarray(roots))

    def batch_idle(self, slots: int) -> BatchLaneState:
        """An all-idle k-lane state: every lane converged, awaiting admit.

        Idle lanes have an empty frontier, so the slice loop's per-lane
        convergence guard freezes them for free — they cost selects, not
        extra supersteps, until :meth:`lane_admit` roots a query into
        them.
        """
        state = self.batch_init(jnp.zeros((slots,), jnp.int32))
        return state._replace(active=jnp.zeros_like(state.active))

    def lane_admit(self, state: BatchLaneState, lane,
                   root) -> BatchLaneState:
        """Overwrite one lane with a freshly-rooted query, others frozen.

        ``lane`` and ``root`` are traced scalars, so admitting into any
        slot reuses one compiled executable.
        """
        key = ("lane_admit",)
        fn = self._loop_cache.get(key)
        if fn is None:
            fresh_lane = self._fresh_lane

            @jax.jit
            def admit(layouts, state, lane, root):
                fresh = fresh_lane(layouts, root)
                return jax.tree.map(
                    lambda full, one: full.at[lane].set(one), state, fresh)

            fn = admit
            self._loop_cache[key] = fn
        return fn(self._layouts, state, jnp.asarray(lane, jnp.int32),
                  jnp.asarray(root, jnp.int32))

    def run_batch_slice(self, state: BatchLaneState,
                        budget) -> BatchLaneState:
        """Advance every live lane by at most ``budget`` supersteps.

        The slice loop is the run-to-convergence loop plus a step budget:
        same cond/body (direction decisions, counters, freeze guards all
        identical), so N slices concatenated partition the exact
        superstep sequence a single ``run_batch`` executes — resuming is
        bit-exact, including per-lane ``'auto'`` direction choices (the
        carried ``direction``/``pull_cost`` registers see the same values
        they would mid-run).  Converged lanes freeze inside the slice;
        the serving plane harvests them (:meth:`lane_done`), frees their
        slots, and admits queued queries without restarting slow lanes.

        ``budget`` is a traced scalar — one compiled executable serves
        every slice length.  Unlike :meth:`run_batch`, slices record no
        physical comm traffic on the translation-time comm manager (the
        serving plane accounts per-harvest via :meth:`lane_stats`).
        """
        faults.trip("lane.superstep")
        return self._slice_fn()(self._layouts, state,
                                jnp.asarray(budget, jnp.int32))

    def _slice_fn(self):
        """The jitted, lane-vmapped budgeted slice loop behind
        :meth:`run_batch_slice`: ``(layouts, state, budget) -> state``."""
        key = ("slice", self._mode)
        fn = self._loop_cache.get(key)
        if fn is None:
            cond, body = self._loop_fns(self._mode)

            def one(layouts, state, budget):
                def scond(s):
                    return jnp.logical_and(cond(s[:-1]), s[-1] < budget)

                def sbody(s):
                    return (*body(layouts, s[:-1]), s[-1] + 1)

                out = jax.lax.while_loop(
                    scond, sbody, (*state, jnp.asarray(0, jnp.int32)))
                return BatchLaneState(*out[:-1])

            fn = jax.jit(jax.vmap(one, in_axes=(None, 0, None)))
            self._loop_cache[key] = fn
        return fn

    def lane_done(self, state: BatchLaneState) -> np.ndarray:
        """Host bool (k,): lane converged (empty frontier or max_iters)."""
        return np.asarray(jnp.logical_or(
            ~jnp.any(state.active, axis=1),
            state.iters >= self.max_iters))

    def lane_stats(self, state: BatchLaneState) -> dict:
        """Per-lane run stats for a sliced batch (same keys as run_batch)."""
        live = jnp.any(state.active, axis=1)
        if self._probe and jnp.issubdtype(state.values.dtype, jnp.floating):
            nanfree = ~jnp.any(jnp.isnan(state.values), axis=1)
        else:
            nanfree = None
        return self._batch_stats(
            state.iters, state.pushes, state.compact, state.switches,
            state.pe_hi, state.pe_lo, state.pe_rows, state.pl_hi,
            state.pl_lo, state.bl_swept, state.bl_skip,
            live=live, nanfree=nanfree)

    def lane_snapshot(self, state: BatchLaneState) -> dict:
        """The full 15-field slice carry as host numpy arrays.

        One ``device_get`` for the whole carry; the returned dict keys
        are exactly :attr:`BatchLaneState._fields`, suitable for
        :func:`repro.core.checkpoint.write_snapshot` and guaranteed to
        round-trip through :meth:`lane_restore` bit-exactly — the carry
        *is* the loop state, so a restored lane continues the identical
        superstep sequence (direction and pull-cost registers included).
        """
        host = jax.device_get(tuple(state))
        return {name: np.asarray(arr)
                for name, arr in zip(BatchLaneState._fields, host)}

    def lane_restore(self, arrays: dict) -> BatchLaneState:
        """Rebuild a :class:`BatchLaneState` from :meth:`lane_snapshot`.

        Dtypes are re-derived from a reference idle state (not trusted
        from the snapshot), so a carry serialized on one platform
        restores with the dtypes this program's compiled loop expects.
        Missing fields raise :class:`CheckpointCorruptError`.
        """
        missing = [f for f in BatchLaneState._fields if f not in arrays]
        if missing:
            raise CheckpointCorruptError(
                f"lane snapshot is missing carry fields: "
                f"{', '.join(missing)}", member=missing[0])
        k = int(np.asarray(arrays["iters"]).shape[0])
        ref = self.batch_idle(k)
        return BatchLaneState(*(
            jnp.asarray(np.asarray(arrays[f]),
                        dtype=getattr(ref, f).dtype)
            for f in BatchLaneState._fields))


# ---------------------------------------------------------------------------
# Translation stage: emit the reduce module from the fused IR op
# ---------------------------------------------------------------------------


def _flat_message_mode(ir: SuperstepIR, fused: FusedGatherReduceOp,
                       program, dtype) -> str:
    """Pick the flat sweep's per-edge message form (all bit-identical):

    * ``'table'`` — weight-free gather (the analyzer's ``weight_use``
      fact, not a hardcoded menu-name list, so *any* user gather whose
      jaxpr never reads the weight qualifies): messages precompute into
      a ``(V+1,)`` masked table, the sweep is ONE gather per slot;
    * ``'masked'`` — the reduce identity absorbs through the gather
      (the ``identity_absorbing`` fact — e.g. SSSP's ``inf + w == inf``):
      the *value* table is pre-masked once and the gather evaluates per
      edge with no separate frontier gather;
    * ``'classic'`` — everything else: per-edge value/degree/frontier
      gathers with explicit identity masking.
    """
    facts = ir.facts if ir.facts is not None else analyze_program(program)
    if facts.weight_use.value is False:
        return "table"
    if program.mask_inactive and facts.identity_absorbing.value:
        return "masked"
    return "classic"


def _slot_major(rows) -> np.ndarray:
    """``(R, 8)`` host rows → ``(8, R)`` device layout.

    On a TPU the minor dimension tiles to 128 lanes, so a gather whose
    index array is ``(R, 8)`` materializes a 16×-padded ``(R, 128)``
    intermediate (measured by a v5e compile: 4.8 GiB of temporaries at
    R = 2^23); slot-major keeps the long axis minor and the sweep's
    temporaries at the logical size.
    """
    return np.ascontiguousarray(np.asarray(rows).T)


def _pull_device_arrays(pplan: G.PullBitmapPlan) -> dict:
    """Host arrays of the dense pull plane, as the engine takes them.

    ``dst``/``wgt`` are the flat width-8 view slot-major (``(8, R8p)``,
    PAD baked to V); ``plan`` keeps the combine and block maps, with the
    row-major flat arrays dropped so they never reach the device.
    """
    return {"dst": _slot_major(pplan.flat_dst_safe),
            "wgt": _slot_major(pplan.flat_wgt),
            "plan": dataclasses.replace(pplan, flat_dst=None,
                                        flat_dst_safe=None, flat_wgt=None)}


def _fwd_device_arrays(fe: G.ForwardELL) -> dict:
    """Host arrays of the forward ELL as the engine takes them: edge slots
    slot-major (``(W, R)``, see :func:`_slot_major`)."""
    return {"row_src": fe.row_src, "dst": _slot_major(fe.dst),
            "wgt": _slot_major(fe.weights),
            "rows_per_vertex": fe.rows_per_vertex}


def _fwd_pe_device_arrays(sfe: G.ShardedForwardELL) -> dict:
    """Per-PE forward-ELL intervals, slot-major within each PE
    (``(pes, W, Rp)``); the leading axis is what ``P("pe")`` splits."""
    def per_pe(a):
        return np.ascontiguousarray(np.swapaxes(np.asarray(a), 1, 2))
    return {"row_src": sfe.row_src, "dst": per_pe(sfe.dst),
            "wgt": per_pe(sfe.weights), "row_valid": sfe.row_valid}


def _pallas_rows(pull: dict, num_vertices: int):
    """Row-major PAD-form ``(R8p, 8)`` edge block for the Pallas kernel
    (transposed in the staged program; the XLA sweep reads slot-major)."""
    dst = pull["dst"]
    return (jnp.where(dst == num_vertices, G.PAD, dst).T, pull["wgt"].T)


def _emit_dense_pull_reduce(ir: SuperstepIR, fused: FusedGatherReduceOp,
                            pplan: G.PullBitmapPlan,
                            schedule: ScheduleConfig, use_pallas: bool):
    """Emit the dense pull partial-reduce module over the flat width-8 view.

    The module contract is ``(layouts, values, active) → (red, got)``
    per-vertex tables; the sweep is rebuilt around
    :class:`~repro.core.graph.PullBitmapPlan`'s flat layout, which the
    device holds slot-major (``layouts["pull"]``, see :func:`_slot_major`):

    * ONE uniform gather + per-sub-row reduce over the 8 slots instead of
      eight per-bucket kernels of different widths (~1.2 vs ~5.8 ns/slot
      measured on XLA:CPU — narrow uniform rows vectorize, tiny buckets
      stop paying per-op dispatch), with the message form picked by
      :func:`_flat_message_mode`;
    * the row→vertex combine is the scatter-free reshape cascade +
      ``row_map`` gather (``pull_bitmap.subrow_combine``) — the old
      per-bucket ``at[sid].min/add/max`` paid ~70 ns/row, ≈4 ms of a
      6.6 ms superstep at 30k rows;
    * ``got`` is computed on a separate gather chain so XLA DCE deletes
      the whole touched-mask plane when the fused superstep never reads
      it (identity-fixpoint applies — see ``FusedSuperstepOp``).

    On the Pallas path the flat arrays stream through the
    ``edge_block_reduce`` kernel (same VMEM vertex-cache addressing,
    uniform width 8) and only the combine cascade runs in XLA.
    Bit-exactness: min/max/integer-add are order-free; float-add keeps a
    deterministic per-vertex lane order (sub-rows reduce in slot order,
    then fold in ascending sub-row order), reassociated relative to the
    pre-flat bucketed sweep within normal reduction noise.
    """
    program = ir.program
    dtype = ir.value_dtype
    V = pplan.num_vertices
    ident = fused.reduce.identity
    op = fused.reduce.op
    gather_module = fused.gather.module
    gather_fn = fused.gather.fn
    mode = _flat_message_mode(ir, fused, program, dtype)
    rop = ROW_REDUCE[op]

    def sub_sweep(values, active, out_deg, dst_blk, wgt_blk):
        """Per-sub-row (red, any) over a slot-major ``(8, n)`` edge block
        (any is lazy).

        ``dst_blk`` uses the *safe* index form (PAD baked to V, the
        message/live tables' always-identity dummy slot) so the hot loop
        runs with no per-superstep PAD masking temp.
        """
        if mode == "table":
            msg_t = pull_bitmap_kernel.message_table(
                values, out_deg, active, gather=gather_module,
                gather_fn=gather_fn, reduce=op,
                mask_inactive=program.mask_inactive, dtype=dtype)
            msg = msg_t[dst_blk]
        elif mode == "masked":
            vmask = jnp.concatenate(
                [jnp.where(active, values, jnp.asarray(ident, dtype)),
                 jnp.full((1,), ident, dtype)])
            deg_t = jnp.concatenate([out_deg, jnp.zeros((1,), jnp.int32)])
            msg = gather_fn(vmask[dst_blk], wgt_blk.astype(dtype),
                            deg_t[dst_blk]).astype(dtype)
        else:
            valid = dst_blk != V
            safe0 = jnp.where(valid, dst_blk, 0)
            v = values[safe0]
            d = out_deg[safe0]
            msg = gather_fn(v, wgt_blk.astype(v.dtype), d)
            live = valid
            if program.mask_inactive:
                live = live & active[safe0]
            msg = jnp.where(live, msg.astype(dtype),
                            jnp.asarray(ident, dtype))
            return rop(msg, axis=0), jnp.any(live, axis=0)

        # lazy any-chain: a separate uint8 gather XLA can DCE wholesale
        def sub_any():
            if program.mask_inactive:
                live_t = jnp.concatenate([active.astype(jnp.uint8),
                                          jnp.zeros((1,), jnp.uint8)])
                return jnp.any(live_t[dst_blk], axis=0)
            return jnp.any(dst_blk != V, axis=0)

        return rop(msg, axis=0), sub_any()

    def partial_reduce(layouts, values, active):
        pull, out_deg = layouts["pull"], layouts["deg"]
        if use_pallas:
            nbr, wgt = _pallas_rows(pull, V)
            sub_red, sub_anyv = kops.edge_block_reduce(
                nbr, wgt, values, out_deg, active,
                gather=gather_module, reduce=op,
                mask_inactive=program.mask_inactive,
                block_rows=schedule.block_rows)
        else:
            sub_red, sub_anyv = sub_sweep(values, active, out_deg,
                                          pull["dst"], pull["wgt"])
        red = pull_bitmap_kernel.subrow_combine(sub_red, pull["plan"],
                                                ident, op, dtype)
        got = pull_bitmap_kernel.subrow_combine(
            sub_anyv.astype(jnp.int8), pull["plan"], 0, "max",
            jnp.int8) != 0
        return red, got

    return partial_reduce, sub_sweep


def _emit_pull_bitmap(ir: SuperstepIR, fstep: FusedSuperstepOp,
                      pplan: G.PullBitmapPlan, fe: G.ForwardELL,
                      reduce_module, sub_sweep, use_pallas: bool,
                      num_edges: int, schedule: ScheduleConfig):
    """Emit the fused bitmap-frontier pull superstep (block-skipping sweep).

    The fused stage the superstep-fusion pass legalized: one jitted
    function carries the whole superstep — touched summary, block
    skipping, gathered sweep, apply, frontier — and returns
    ``(new_values, next_active, (swept_edges, blocks_swept,
    blocks_skipped))`` so the run loop can account the *real* pull cost
    instead of assuming a full-E sweep.

    Structure per superstep (all branches bit-exact, like the push tiers):

    * ``r_f`` (live forward-ELL rows) gates the whole plane: beyond the
      largest pre-pass capacity tier the superstep runs the dense full
      sweep directly — the touched pre-pass itself costs O(capacity·W)
      scatter, which a wide frontier would waste (stats then report all
      blocks swept, ``swept_edges = E``).
    * narrow frontiers build the **touched table** (compacted forward
      scatter of frontier bits, capacity tier picked from ``r_f`` exactly
      like the push engine), take exact per-block liveness over the flat
      view's uniform blocks, and either compact live block ids into a
      capacity tier (:data:`~repro.core.scheduler.PULL_BLOCK_TIERS`
      fractions of the block count, XLA path — the cumsum+searchsorted
      idiom on the packed liveness bitmap) and gather just those blocks,
      or run the whole Pallas grid with the per-block early-out
      (``edge_block_reduce(block_live=...)``, TPU path).  Both reduce
      swept sub-rows densely and assemble the table through the
      scatter-free combine cascade.
    * the touched table doubles as the ``got`` mask (bit-identical to the
      dense module's: both mean "some live in-edge"), and identity-
      fixpoint applies (``fstep.touched_free``) skip the mask entirely.

    Reads ``layouts["pull"]`` (slot-major flat view + block maps) and
    ``layouts["fwd"]`` (the forward ELL of the touched pre-pass).
    """
    fused = fstep.fused
    apply_fn = fstep.apply.fn
    dtype = ir.value_dtype
    V = pplan.num_vertices
    ident = fused.reduce.identity
    op = fused.reduce.op
    caps = pull_block_capacities(pplan.num_blocks)
    b_total = pplan.num_blocks
    br = pplan.block_rows
    r8p = pplan.num_subrows

    def finish(values, red, got, swept_e, swept_b):
        if fstep.touched_free:
            new = apply_fn(values, red)       # untouched are fixpoints
        else:
            new = jnp.where(got, apply_fn(values, red), values)
        nxt = new != values
        return new, nxt, (swept_e, swept_b,
                          jnp.asarray(b_total, jnp.int32) - swept_b)

    def sweep_gathered(layouts, cap, touched, live, values, active):
        """XLA path: compact live block ids, gather their sub-rows."""
        pull = layouts["pull"]
        nb = b_total
        selb, okb = G.bitmap_select(G.pack_bits(live), cap)
        rows = (selb[:, None] * br
                + jnp.arange(br, dtype=jnp.int32)[None, :]).reshape(-1)
        okr = jnp.repeat(okb, br)
        dst_blk = jnp.where(okr[None, :], pull["dst"][:, rows], V)
        sub, _ = sub_sweep(values, active, layouts["deg"], dst_blk,
                           pull["wgt"][:, rows])
        # expand back to the full sub-row space GATHER-side: a tiny
        # inverse-map scatter (cap entries) + two cheap gathers, instead
        # of scatter-setting cap·block_rows sub-results (~60 ns/el — it
        # dominated the whole compacted superstep at the large tier)
        inv = jnp.full((nb + 1,), cap, jnp.int32).at[
            jnp.where(okb, selb, nb)].set(
                jnp.where(okb, jnp.arange(cap, dtype=jnp.int32), cap))
        buffer = jnp.concatenate([sub.astype(dtype),
                                  jnp.full((br,), ident, dtype)])
        sub_idx = jnp.arange(r8p, dtype=jnp.int32)
        slot = inv[sub_idx // br]              # cap → the identity tail
        sub_full = buffer[slot * br + sub_idx % br]
        return pull_bitmap_kernel.subrow_combine(sub_full, pull["plan"],
                                                 ident, op, dtype)

    def sweep_pallas(layouts, touched, live, values, active):
        """Pallas path: full grid, per-block in-kernel early-out.

        Skipping happens at the Pallas grid's ``schedule.block_rows``
        granularity (coarser than the plan's blocks); stats still account
        at plan-block granularity, conservatively rounded up to the grid
        blocks that actually ran — the two paths are bit-exact in results
        but may legitimately differ in their skip counts.
        """
        pull = layouts["pull"]
        t8 = touched[pull["plan"].owner8] != 0
        grid_br = schedule.block_rows
        pad = (-r8p) % grid_br
        grid_live = jnp.pad(t8, (0, pad)).reshape(-1, grid_br).any(axis=1)
        nbr, wgt = _pallas_rows(pull, V)
        sub_red, _ = kops.edge_block_reduce(
            nbr, wgt, values, layouts["deg"], active,
            gather=fused.gather.module, reduce=op, mask_inactive=True,
            block_rows=grid_br, block_live=grid_live)
        red = pull_bitmap_kernel.subrow_combine(sub_red, pull["plan"],
                                                ident, op, dtype)
        if grid_br % br == 0:
            fine_live = jnp.repeat(grid_live, grid_br // br)[:b_total]
        else:    # odd grid: account conservatively at fine granularity
            fine_live = live
        return red, fine_live

    def narrow(cap):
        """One compacted tier: pre-pass + block skip + sweep.

        ``cap`` (static) sizes the pre-pass row buffer soundly: on this
        branch ``m_f ≤ cap`` and the live forward-ELL row count
        ``r_f = Σ ceil(deg/W) ≤ m_f``, so one m_f comparison routes the
        whole superstep and no nested capacity conditional is needed for
        the pre-pass (XLA:CPU conditionals carry real per-superstep
        overhead, measured ~0.3 ms each).

        The *live-block* count has no such m_f bound — touched hubs span
        ``ceil(in_deg/block_slots)`` blocks each, so a handful of frontier
        edges can light hundreds of blocks (measured: m_f=57 lights ~430
        of 12k blocks on the 50k R-MAT, hub in-neighborhoods).  The block
        select capacity is therefore 4× the routing threshold (gather
        cost stays proportional to capacity, still far under the dense
        sweep), and the XLA gathered sweep keeps one safety conditional
        on the exact count (falling back to the dense sweep,
        bit-identical); the Pallas early-out grid has no capacity to
        overflow and needs no guard.
        """
        block_cap = min(4 * cap, b_total)

        def f(layouts, values, active):
            fwd, plan_d = layouts["fwd"], layouts["pull"]["plan"]
            touched = pull_bitmap_kernel.touched_table(
                fwd["row_src"], fwd["dst"], active, num_rows=fe.num_rows,
                capacity=cap, num_vertices=V)
            live = pull_bitmap_kernel.block_liveness(touched, plan_d.owner8,
                                                     br)
            got = touched[:V] != 0

            if use_pallas:
                red, fine_live = sweep_pallas(layouts, touched, live,
                                              values, active)
                swept_e = jnp.sum(jnp.where(fine_live, plan_d.block_edges,
                                            0))
                return finish(values, red, got, swept_e,
                              jnp.sum(fine_live.astype(jnp.int32)))

            def compacted(values, active):
                red = sweep_gathered(layouts, block_cap, touched, live,
                                     values, active)
                swept_e = jnp.sum(jnp.where(live, plan_d.block_edges, 0))
                return finish(values, red, got, swept_e,
                              jnp.sum(live.astype(jnp.int32)))

            def overflow(values, active):
                red, got_d = reduce_module(layouts, values, active)
                return finish(values, red, got_d,
                              jnp.asarray(num_edges, jnp.int32),
                              jnp.asarray(b_total, jnp.int32))

            cnt = jnp.sum(live.astype(jnp.int32))
            return jax.lax.cond(cnt <= block_cap, compacted, overflow,
                                values, active)
        return f

    def wide(layouts, values, active):
        red, got = reduce_module(layouts, values, active)
        return finish(values, red, got, jnp.asarray(num_edges, jnp.int32),
                      jnp.asarray(b_total, jnp.int32))

    @jax.jit
    def pull_superstep(layouts, values, active):
        # single-switch routing on m_f alone: the frontier's out-edge
        # count bounds every dynamic size in a compacted superstep (live
        # forward-ELL rows, touched vertices, live blocks), so the tier
        # whose capacity covers m_f is guaranteed sufficient — and a
        # frontier too wide for the largest tier takes the dense sweep
        # without ever paying the pre-pass
        m_f = jnp.sum(jnp.where(active, layouts["deg"], 0))
        tier = (m_f > caps[0]).astype(jnp.int32) \
            + (m_f > caps[1]).astype(jnp.int32)
        return jax.lax.switch(tier, [narrow(caps[0]), narrow(caps[1]),
                                     wide], layouts, values, active)

    return pull_superstep, caps


def _wrap_superstep_stats(superstep, num_edges: int):
    """Lift a ``(layouts, values, active) → (values, active)`` superstep
    to the run loop's stats-carrying contract: non-bitmap pull planes
    sweep all E edges every superstep and have no block accounting
    (zeros)."""
    z = jnp.asarray(0, jnp.int32)
    e = jnp.asarray(num_edges, jnp.int32)

    def wrapped(layouts, values, active):
        new, nxt = superstep(layouts, values, active)
        return new, nxt, (e, z, z)

    return wrapped


def _emit_segment_scan_reduce(ir: SuperstepIR, fused: FusedGatherReduceOp,
                              reverse_coo: tuple, num_vertices: int,
                              num_edges: int, splan: SchedulePlan):
    """Emit the sparse chunk-streamed partial-reduce module.

    ``pipelines`` → ``lax.scan`` over edge chunks (bounds the live working
    set).  The chunk geometry comes verbatim from the
    :class:`~repro.core.scheduler.SchedulePlan` — the plan already rounded
    the chunk count to a multiple of the resolved PEs and guarded the
    edgeless degenerate, so what this module stages is exactly what
    ``SchedulePlan.describe()`` reports.  ``reverse_coo`` is the cached
    COO of the transposed graph
    (:meth:`~repro.core.preprocess.GraphLayouts.reverse_coo`).

    Returns ``(scan_chunks, host_chunks, nchunk)``: ``scan_chunks(chunks,
    out_deg, values, active, varying=False)`` reduces the given chunk
    arrays (all of them, or one PE's slice under ``shard_map``), and
    ``host_chunks`` are the ``(nchunk, chunk_size)`` host arrays the
    translator places as ``layouts["coo"]``.
    """
    program = ir.program
    dtype = ir.value_dtype
    V = num_vertices
    E = num_edges
    ident = fused.reduce.identity
    reduce_op = fused.reduce.op
    gather_fn = fused.gather.fn

    # COO of the reversed graph: edge (u → v) appears as (dst=v, src=u)
    seg_dst, src, wts = (np.asarray(a) for a in reverse_coo)
    nchunk = splan.num_chunks
    csize = splan.chunk_size
    pad = nchunk * csize - E
    PADV = np.iinfo(np.int32).max
    host_chunks = (
        np.pad(seg_dst, (0, pad),
               constant_values=PADV).reshape(nchunk, csize),
        np.pad(src, (0, pad)).reshape(nchunk, csize),
        np.pad(wts, (0, pad)).reshape(nchunk, csize))

    def scan_chunks(chunks, out_deg, values, active, varying=False):
        def chunk(carry, xs):
            red_table, got_table = carry
            seg, srcs, ws = xs
            valid = seg != PADV
            safe_src = jnp.where(valid, srcs, 0)
            v = values[safe_src]
            d = out_deg[safe_src]
            msg = gather_fn(v, ws.astype(v.dtype), d)
            live = valid
            if program.mask_inactive:
                live = live & active[safe_src]
            msg = jnp.where(live, msg.astype(dtype), ident)
            safe_seg = jnp.where(valid, seg, 0)
            if reduce_op == "add":
                red_table = red_table.at[safe_seg].add(jnp.where(live, msg, 0))
            elif reduce_op == "min":
                red_table = red_table.at[safe_seg].min(msg)
            else:
                red_table = red_table.at[safe_seg].max(msg)
            got_table = got_table.at[safe_seg].max(live)
            return (red_table, got_table), None

        init = (jnp.full((V,), ident, dtype), jnp.zeros((V,), bool))
        if varying:   # per-PE slices are pe-varying
            init = jax.tree.map(
                lambda a: jax.lax.pcast(a, ("pe",), to="varying"), init)
        (red_table, got_table), _ = jax.lax.scan(chunk, init, chunks)
        return red_table, got_table

    return scan_chunks, host_chunks, nchunk


def _emit_push_scatter(ir: SuperstepIR, push_op: PushScatterOp, g: G.Graph,
                       splan: SchedulePlan):
    """Emit the legacy chunk-streamed push scatter (``layout='coo_chunks'``).

    Streams the *forward* CSR's COO chunks (no transpose — ``g`` already
    holds out-edges), scattering messages from active sources with
    ``at[].add/min/max``; chunks with no active source are skipped via
    ``lax.cond``.  Kept for the sparse backend, which builds no forward
    ELL; the dense backend uses :func:`_emit_push_ell` instead.  Returns
    ``(partial_reduce, chunks)``; the translator places ``chunks`` as
    ``layouts["push_coo"]``.
    """
    dtype = ir.value_dtype
    V = g.num_vertices
    src, dst, wts = G.coo_arrays(g)
    chunks = push_kernel.chunk_coo(dst, src, wts,
                                   num_chunks=splan.num_chunks)
    ident = push_op.reduce.identity
    gather_fn = push_op.gather.fn
    reduce_op = push_op.reduce.op

    def partial_reduce(layouts, values, active):
        dst_c, src_c, wgt_c = layouts["push_coo"]
        return push_kernel.push_scatter_reduce(
            dst_c, src_c, wgt_c, values, layouts["deg"], active,
            gather_fn=gather_fn, reduce=reduce_op, identity=ident,
            num_vertices=V, dtype=dtype)

    return partial_reduce, chunks


def _emit_push_ell(ir: SuperstepIR, push_op: PushScatterOp,
                   fe: G.ForwardELL, apply_fn, pull_reduce_module,
                   use_pallas: bool):
    """Emit the frontier-compacted forward-ELL push superstep.

    Builds the tiered push superstep: the live forward-ELL row count
    ``r_f`` picks, per superstep, the smallest compaction capacity tier
    that covers the frontier (``kernels/push_ell.py`` does cumsum
    compaction → gather → segment-reduce), or the *dense fallback* — the
    same masked sweep as the pull module — when the frontier is too wide
    for compaction to beat the dense stream.  All branches compute the
    identical superstep function, so the tier choice (like the direction
    choice) is invisible in the results.

    The compacted branches apply the reduced table *everywhere* and skip
    the touched mask entirely — sound because the fusion pass binds the
    ``fwd_ell`` layout only after probing ``apply(x, identity) == x``
    (untouched vertices are fixpoints).  Reads ``layouts["fwd"]``.
    Returns ``(push_superstep, tiers, live_rows)``, where ``live_rows``
    is the run loop's ``(1,)`` live-row counter.
    """
    dtype = ir.value_dtype
    V = fe.num_vertices
    ident = push_op.reduce.identity
    gather_fn = push_op.gather.fn
    gather_module = push_op.gather.module
    reduce_op = push_op.reduce.op
    tiers = push_capacity_tiers(fe.num_rows)
    interpret = jax.default_backend() != "tpu"

    def live_rows(layouts, active):
        rows_per_v = layouts["fwd"]["rows_per_vertex"]
        return jnp.sum(jnp.where(active, rows_per_v, 0))[None]

    def compacted_branch(capacity):
        def branch(layouts, values, active):
            fwd = layouts["fwd"]
            red, _ = push_ell_kernel.push_ell_reduce(
                fwd["row_src"], fwd["dst"], fwd["wgt"], values,
                layouts["deg"],
                active, num_rows=fe.num_rows, capacity=capacity,
                gather_fn=gather_fn, reduce=reduce_op, identity=ident,
                num_vertices=V, dtype=dtype, gather_module=gather_module,
                use_pallas=use_pallas, interpret=interpret,
                emit_touched=False)
            new = apply_fn(values, red)
            return new, new != values
        return branch

    def dense_fallback(layouts, values, active):
        # the pull module's masked sweep (bit-identical reduce); the
        # fwd_ell layout already guarantees an identity-fixpoint apply,
        # so applying everywhere matches take-if-touched bit-for-bit and
        # the unused `got` chain is dead code XLA deletes
        red, _ = pull_reduce_module(layouts, values, active)
        new = apply_fn(values, red)
        return new, new != values

    branches = [compacted_branch(c) for c in tiers] + [dense_fallback]

    @jax.jit
    def push_superstep(layouts, values, active):
        r_f = live_rows(layouts, active)[0]
        tier = sum((r_f > c).astype(jnp.int32) for c in tiers)
        return jax.lax.switch(tier, branches, layouts, values, active)

    return push_superstep, tiers, live_rows


def _emit_push_ell_sharded(ir: SuperstepIR, push_op: PushScatterOp,
                           sfe: G.ShardedForwardELL, apply_fn,
                           pull_reduce_module, use_pallas: bool, mesh,
                           xop: ExchangeOp):
    """Emit the multi-PE frontier-compacted push superstep (``shard_map``).

    The paper's ``pipelines × PEs`` runtime applied to the push plane:
    each PE owns one contiguous, degree-balanced forward-ELL row interval
    (:class:`~repro.core.graph.ShardedForwardELL`) and runs the same
    three-stage engine as the single-PE path — interval-local frontier
    compaction (row ids stay PE-local), gather/message over the
    ``(capacity, W)`` block, one segment-combine into a full-width partial
    vertex table — inside ``shard_map`` over the ``pe`` mesh axis.  The
    disjoint per-PE partials then combine with the reduce-matched
    collective (psum/pmin/pmax), after which the table is replicated and
    the apply runs like the single-PE engine's.

    Capacity tiers are shared across PEs (``shard_map`` traces one SPMD
    program, so buffer shapes must agree), derived from the *largest*
    interval's row count; each PE still picks its own tier per superstep
    from its local live row count — the ``lax.switch`` is collective-free,
    so PEs may diverge.  The *fallback* decision is global (``max`` of the
    per-PE live counts, computed on the replicated frontier): a wide
    frontier routes the whole superstep to the replicated dense masked
    sweep — the identical pull module, no exchange — so every PE takes the
    same branch and the collective inside the sharded branch can never
    deadlock.

    Returns ``(push_superstep, tiers, live_rows_per_pe)`` where the last
    is the ``(pes,)`` live-row counter the run loop uses for the
    tier/fallback stat and the per-PE load-balance stats.  Reads
    ``layouts["fwd_pe"]``, which the translator places on ``P("pe")`` so
    each PE holds only its own interval.
    """
    dtype = ir.value_dtype
    V = sfe.num_vertices
    ident = push_op.reduce.identity
    gather_fn = push_op.gather.fn
    gather_module = push_op.gather.module
    reduce_op = push_op.reduce.op
    tiers = push_capacity_tiers(sfe.rows_per_pe_max)
    interpret = jax.default_backend() != "tpu"
    collective = _COLLECTIVES[xop.collective]
    rp = sfe.rows_per_pe_max

    def live_rows_per_pe(layouts, active):
        """(pes,) live rows — per-PE partials of the frontier occupancy."""
        fwd = layouts["fwd_pe"]
        return jnp.sum(active[fwd["row_src"]] & fwd["row_valid"],
                       axis=1).astype(jnp.int32)

    def pe_partial(rs, dstb, wgtb, valid, out_deg, values, active):
        rs, dstb, wgtb, valid = rs[0], dstb[0], wgtb[0], valid[0]
        live = active[rs] & valid       # interval-local live-row mask
        r_f = jnp.sum(live.astype(jnp.int32))

        def branch(capacity):
            def b(values):
                red, _ = push_ell_kernel.compacted_push_reduce(
                    rs, dstb, wgtb, live, values, out_deg,
                    num_rows=rp, capacity=capacity, gather_fn=gather_fn,
                    reduce=reduce_op, identity=ident, num_vertices=V,
                    dtype=dtype, gather_module=gather_module,
                    use_pallas=use_pallas, interpret=interpret,
                    emit_touched=False)
                return red
            return b

        tier = sum((r_f > c).astype(jnp.int32) for c in tiers[:-1])
        red = jax.lax.switch(tier, [branch(c) for c in tiers], values)
        # disjoint partials: the reduce-matched collective is exact
        return collective(red, "pe")

    def sharded_compacted(layouts, values, active):
        # unchecked: the vma checker cannot type the per-PE switch before
        # the collective, nor this body when run_batch vmaps the loop
        # (see shard_map_unchecked); the output is genuinely replicated —
        # the collective runs unconditionally
        fwd = layouts["fwd_pe"]
        red = shard_map_unchecked(pe_partial, mesh=mesh,
                                  in_specs=(P("pe"), P("pe"), P("pe"),
                                            P("pe"), P(), P(), P()),
                                  out_specs=P())(
            fwd["row_src"], fwd["dst"], fwd["wgt"], fwd["row_valid"],
            layouts["deg"], values, active)
        new = apply_fn(values, red)
        return new, new != values

    def dense_fallback(layouts, values, active):
        # the pull module's masked sweep, replicated (no exchange); the
        # identity-fixpoint apply lets it skip the touched mask (as in
        # the single-PE engine)
        red, _ = pull_reduce_module(layouts, values, active)
        new = apply_fn(values, red)
        return new, new != values

    @jax.jit
    def push_superstep(layouts, values, active):
        wide = jnp.max(live_rows_per_pe(layouts, active)) > tiers[-1]
        return jax.lax.cond(wide, dense_fallback, sharded_compacted,
                            layouts, values, active)

    return push_superstep, tiers, live_rows_per_pe


def _emit_exchange(xop: ExchangeOp, scan_chunks, mesh,
                   quantized: bool = False, num_vertices: int = 0):
    """Emit the cross-PE combine around the partial-reduce module.

    Each PE owns an edge-chunk slice (paper: edge partitions per PE):
    ``layouts["coo"]`` is placed on ``P("pe")``, so every PE scans only
    its own ``nchunk / pes`` chunks.  Vertex tables replicate and combine
    with the reduce-matched collective — psum for 'add' is only correct
    because the edge sets are disjoint per PE.

    ``quantized`` swaps the full-precision psum for
    :meth:`~repro.core.comm.CommManager.quantized_psum` (int8 wire format
    with a pmax-agreed shared scale).  The caller only sets it for *float
    add* combines — min/max and integer-add exchanges keep the exact
    collective, the bit-exactness escape hatch.

    The frontier half of the exchange (the touched mask) ships as a
    **packed bitmap** (V/32 uint32 words, OR-combined via
    :meth:`~repro.core.comm.CommManager.bitmap_or`) when ``pes < 16`` —
    V/8 bytes per table instead of the int8 ``pmax`` ring's V bytes, an
    8× wire reduction at p=2 that decays to break-even at p=16, where the
    int8 ring form is kept.  Both forms are bit-exact (OR of packed words
    ≡ pmax of the unpacked mask).
    """
    collective = _COLLECTIVES[xop.collective]
    pack_mask = xop.pes < 16
    if quantized:
        assert xop.collective == "psum", "quantization is add-only"
        collective = functools.partial(CommManager.quantized_psum,
                                       pes=xop.pes)

    def sharded_reduce(layouts, values, active):
        def pe_body(chunks, out_deg, values, active):
            red, got = scan_chunks(chunks, out_deg, values, active,
                                   varying=True)
            red = collective(red, "pe")
            if pack_mask:
                words = CommManager.bitmap_or(G.pack_bits(got), "pe",
                                              pes=xop.pes)
                got = G.unpack_bits(words, num_vertices)
            else:
                got = jax.lax.pmax(got.astype(jnp.int8), "pe") != 0
            return red, got

        # unchecked: the quantized combine ends in all_gather + local sum,
        # whose replicated-ness the vma checker cannot infer (nor that of
        # a vmapped shard_map — see shard_map_unchecked).  The outputs
        # are genuinely replicated: every PE computes the same combine of
        # the same gathered parts.
        return shard_map_unchecked(pe_body, mesh=mesh,
                                   in_specs=((P("pe"),) * 3, P(), P(), P()),
                                   out_specs=(P(), P()))(
            layouts["coo"], layouts["deg"], values, active)

    return sharded_reduce


# ---------------------------------------------------------------------------
# The translator
# ---------------------------------------------------------------------------

# Staging cache: (program, graph structure-array ids, schedule, pallas
# flag) → the emitted supersteps + shared loop cache.  A repeat translate()
# of the same inputs skips stage 3 *and* AOT (the jitted function objects
# persist, so their compiled executables do too).  The graph is keyed by
# the identity of its structure arrays — the same key the layout cache
# uses — so a staging hit survives layout-cache eviction (the caller's
# graph still holds the same arrays); hits verify array identity against
# the entry's pinned layouts, so a recycled id can never alias.
# Unhashable programs (array init_value) simply skip the cache.
_STAGING_CACHE: collections.OrderedDict = collections.OrderedDict()
_STAGING_CACHE_MAX = 16


def staging_cache_clear() -> None:
    """Drop every staged superstep (memory-pressure / test hook).

    Staged entries pin their :class:`~repro.core.preprocess.GraphLayouts`
    (and thus the graph arrays and compiled executables), so freeing
    layout memory requires clearing *both* caches:
    ``translator.staging_cache_clear()`` then
    ``preprocess.layout_cache_clear()``.
    """
    _STAGING_CACHE.clear()


def _staging_key(program, g, schedule, use_pallas):
    try:
        hash(program)          # arrays in init_value make a program unhashable
        hash(schedule)
    except TypeError:
        return None
    # the program object itself (not its hash) so lookups equality-check:
    # a hash collision must never alias two distinct programs
    return (program, preprocess._layout_key(g), schedule, use_pallas)


def _staging_hit(staged, g):
    """A cached entry is valid iff it pins this graph's exact arrays."""
    held = staged["layouts"].graph
    return held.edges_dst is g.edges_dst \
        and held.edge_offsets is g.edge_offsets \
        and held.edge_weights is g.edge_weights


def translate(
    program: VertexProgram,
    g: G.Graph,
    schedule: ScheduleConfig | None = None,
    comm: CommManager | None = None,
    *,
    use_pallas: bool = False,
    aot_compile: bool = True,
    dump_passes: bool = False,
    validate: bool = False,
    strict: bool = False,
    checkpoint_dir: str | None = None,
    checkpoint_every: int | None = None,
) -> CompiledGraphProgram:
    """Stage a DSL program into a specialized executable for graph ``g``.

    Lowers the program to :class:`~repro.core.ir.SuperstepIR`, runs the
    default pass pipeline, then walks the optimized IR to emit the jitted
    superstep.  ``dump_passes=True`` additionally records the per-pass
    before/after IR dumps on ``report.pass_report``.

    The pipeline's structured findings (overflow risks, probe-only
    decisions, lattice pathologies — see :mod:`repro.core.diagnostics`)
    surface on ``report.diagnostics``; ``strict=True`` refuses to stage a
    program carrying any warning- or error-severity finding, raising
    :class:`~repro.errors.DiagnosticError` with the full tuple attached.

    Messages flow along in-edges (pull form): ``g`` holds out-edges (CSR),
    so the translator takes the transposed adjacency — and every other
    derived layout — from the graph-keyed preprocessing cache
    (:func:`repro.core.preprocess.layouts_for`); repeated translates of
    the same graph re-bucket nothing.  The emitted supersteps themselves
    are memoized per (program, graph, schedule) in the staging cache, so a
    repeat translate of identical inputs costs milliseconds;
    ``report.translate_breakdown`` itemizes where the time went.
    """
    t0 = time.perf_counter()
    schedule = schedule or ScheduleConfig()
    comm = comm or CommManager()
    if validate:
        # opt-in structural validation — resident graphs check the CSR
        # invariants directly; partition containers replay the same checks
        # per partition on top of their always-on streamed-fetch checksums
        if isinstance(g, G.Graph):
            G.validate_graph(g, reduce=program.reduce)
        elif hasattr(g, "validate_partitions"):
            g.validate_partitions(reduce=program.reduce)
    splan: SchedulePlan = plan(schedule, num_vertices=g.num_vertices,
                               num_edges=g.num_edges,
                               fixed_partitions=getattr(g, "partitions", None))

    # out-of-core dispatch: a multi-partition plan, or a graph source that
    # is not resident (a partition container), stages onto the streamed
    # engine — translate-time and run-time data placement diverge there,
    # so none of the resident emit paths below apply
    if splan.num_partitions > 1 or not isinstance(g, G.Graph):
        from . import stream
        return stream.translate_partitioned(
            program, g, schedule, splan, comm, use_pallas=use_pallas,
            dump_passes=dump_passes, strict=strict,
            checkpoint_dir=checkpoint_dir,
            checkpoint_every=checkpoint_every)

    # ---- stages 1+2: lower to IR, run the pass pipeline -----------------
    # (always re-run: the pipeline costs ~ms and keeps reports/dumps fresh)
    t_passes0 = time.perf_counter()
    ctx = PassContext(schedule=schedule, plan=splan, use_pallas=use_pallas,
                      num_vertices=g.num_vertices, num_edges=g.num_edges)
    ir, pipeline_report = default_pipeline().run(
        lower_program(program), ctx, dump=dump_passes)
    passes_s = time.perf_counter() - t_passes0
    # the analysis pass's share of passes_s — near-zero on a fact-cache
    # hit (templates are memoized, so repeat translates always hit)
    analysis_s = next((r.time_s for r in pipeline_report.records
                       if r.name == "program-analysis"), 0.0)
    if strict and max_severity(ctx.diagnostics) in ("warning", "error"):
        raise DiagnosticError(
            f"strict translation rejected {program.name!r}: " +
            "; ".join(d.render() for d in ctx.diagnostics
                      if d.severity != "info"),
            diagnostics=tuple(ctx.diagnostics))

    fstep = ir.find(FusedSuperstepOp)
    if fstep is not None:
        fused, apply_op, frontier_op = fstep.fused, fstep.apply, \
            fstep.frontier
    else:
        fused = ir.find(FusedGatherReduceOp)
        apply_op = ir.find(ApplyOp)
        frontier_op = ir.find(FrontierUpdateOp)
    exchange_op = ir.find(ExchangeOp)
    assert fused is not None and apply_op is not None \
        and frontier_op is not None, "pass pipeline left the IR incomplete"

    dtype = ir.value_dtype
    V = g.num_vertices
    push_op = ir.find(PushScatterOp)
    policy = splan.direction

    key = _staging_key(program, g, schedule, use_pallas)
    staged = _STAGING_CACHE.get(key) if key is not None else None
    if staged is not None and _staging_hit(staged, g):
        _STAGING_CACHE.move_to_end(key)
        preprocess_s = emit_s = 0.0
        cached = True
    else:
        lay = preprocess.layouts_for(g)
        staged = _stage(program, ir, g, lay, schedule, splan, use_pallas,
                        fstep, fused, apply_op, frontier_op, exchange_op,
                        push_op if policy.mode != "pull" else None, comm)
        preprocess_s = staged.pop("preprocess_s")
        emit_s = staged.pop("emit_s")
        cached = False
        if key is not None:
            _STAGING_CACHE[key] = staged
            _STAGING_CACHE.move_to_end(key)
            while len(_STAGING_CACHE) > _STAGING_CACHE_MAX:
                _STAGING_CACHE.popitem(last=False)

    superstep = staged["superstep"]
    push_superstep = staged["push_superstep"]
    init_state = staged["init_state"]
    layouts = staged["device_layouts"]
    max_iters = schedule.superstep_budget(program.max_iters, V)

    # AOT compile so translation time includes staging (paper's TT metric).
    # Executing once (rather than .lower().compile()) populates the normal
    # jit call cache, which the staging cache then reuses across repeats.
    t_aot0 = time.perf_counter()
    if aot_compile and not staged["aot_done"]:
        v0, a0 = init_state(layouts, roots=0 if program.frontier == "changed"
                            else None)
        jax.block_until_ready(superstep(layouts, v0, a0))
        if push_superstep is not None:
            jax.block_until_ready(push_superstep(layouts, v0, a0))
        staged["aot_done"] = True
    aot_s = time.perf_counter() - t_aot0

    tt = time.perf_counter() - t0
    exchange_plane = staged["exchange_plane"]
    est_collective = comm.estimate_collective_bytes(
        V, dtype, staged["pes"] if exchange_plane is not None else 1,
        quantized=staged["exchange_quantized"])
    # the sharded pull plane additionally ships the touched mask each
    # superstep — as a packed bitmap below 16 PEs (V/8 bytes per table)
    est_frontier = comm.estimate_frontier_bytes(
        V, staged["pes"] if exchange_plane == "pull" else 1,
        packed=staged["pes"] < 16)
    report = TranslationReport(
        program=program.name,
        backend=ir.backend,
        gather_module=fused.gather.module,
        reduce_module=fused.reduce.op,
        pipelines=splan.num_chunks,
        pes=staged["pes"],
        translate_time_s=tt,
        est_flops_per_superstep=2.0 * g.num_edges,
        est_bytes_per_superstep=float(g.num_edges * (4 + 4 + dtype.itemsize)),
        est_collective_bytes=est_collective,
        est_frontier_bytes=est_frontier,
        diagnostics=tuple(ctx.diagnostics),
        pass_report=(render_table(ctx.diagnostics, title="-- diagnostics --")
                     + "\n\n" + pipeline_report.render())
        if dump_passes else None,
        ir_dump=ir.dump(),
        direction_policy=policy.describe(),
        directions=("pull", "push") if push_superstep is not None
        else ("pull",),
        translate_breakdown={
            "preprocess_s": preprocess_s, "passes_s": passes_s,
            "analysis_s": analysis_s,
            "emit_s": emit_s, "aot_s": aot_s, "total_s": tt,
            "staging_cached": cached,
            "preprocess_cached": staged["preprocess_cached"] or cached},
        push_layout=staged["push_layout"],
        push_tiers=staged["push_tiers"],
        staged_chunks=staged["chunk_geometry"],
        exchange_plane=exchange_plane,
        exchange_quantized=staged["exchange_quantized"],
        push_pe_rows=staged["push_pe_rows"],
        push_pe_edges=staged["push_pe_edges"],
        pull_sweep=staged["pull_sweep"],
        pull_block_tiers=staged["pull_block_tiers"],
        pull_blocks_total=staged["pull_blocks_total"],
    )
    return CompiledGraphProgram(
        superstep, init_state, report, max_iters, layouts=layouts,
        push_superstep=push_superstep, direction=policy, num_vertices=V,
        num_edges=g.num_edges,
        push_tiers=staged["push_tiers"], loop_cache=staged["loop_cache"],
        push_rf_fn=staged["push_rf_fn"],
        push_stat_pes=staged["push_stat_pes"], comm=comm,
        exchange_plane=exchange_plane,
        collective_bytes_per_superstep=est_collective + est_frontier,
        probe_divergence=schedule.probe_divergence,
        checkpoint_dir=checkpoint_dir, checkpoint_every=checkpoint_every,
        fingerprints_fn=lambda: ckpt.run_fingerprints(program, g, schedule))


def _place_layouts(host: dict, comm: CommManager, mesh) -> dict:
    """Place the staged functions' layout arrays, once, through ``comm``.

    One device holds everything when ``mesh`` is None.  Under a multi-PE
    plan the per-PE partitions (``"fwd_pe"``, ``"coo"``) go on
    ``P("pe")`` — each PE holds only its own interval or chunk slice — and
    the rest is replicated on the mesh.  Staged programs take these as jit
    *arguments*, never as baked HLO constants.
    """
    if mesh is None:
        return comm.transport(host)
    split = {k: host.pop(k) for k in ("fwd_pe", "coo") if k in host}
    placed = comm.transport(host, P(), mesh=mesh)
    placed.update(comm.transport(split, P("pe"), mesh=mesh))
    return placed


def _stage(program, ir, g, lay, schedule, splan, use_pallas, fstep, fused,
           apply_op, frontier_op, exchange_op, push_op, comm):
    """Stage 3 proper: walk the optimized IR, emit the jitted supersteps.

    Returns the staging-cache entry; graph-derived layouts come from
    ``lay`` (built on first use, memoized per graph) and are placed on the
    device here (the entry's ``device_layouts``), and the entry's
    ``preprocess_s`` records only the layout seconds spent *this* call.
    """
    pre_before = sum(lay.build_times_s.values())
    t_emit0 = time.perf_counter()
    dtype = ir.value_dtype
    V = g.num_vertices
    # the graph arrays every staged function takes as its first argument
    # (placed once, below); "coo"/"fwd_pe" are the per-PE partitions
    host = {"deg": np.asarray(g.out_degrees, np.int32),
            "init": program.materialize_init(V)}

    pes = 1 if exchange_op is None else exchange_op.pes
    # which plane exchanges across PEs (for runtime transfer accounting):
    # the sparse backend shards the pull sweep, the dense backend shards
    # the compacted push engine (resolved below), a single PE shards none
    exchange_plane = None
    exchange_quantized = False
    chunk_geometry = None
    pplan = None

    sub_sweep = None
    if fused.kernel == "edge_block":
        pplan = lay.pull_plan(schedule.pull_block_slots)
        host["pull"] = _pull_device_arrays(pplan)
        reduce_module, sub_sweep = _emit_dense_pull_reduce(
            ir, fused, pplan, schedule, use_pallas)
    else:
        scan_chunks, host["coo"], nchunk = _emit_segment_scan_reduce(
            ir, fused, lay.reverse_coo(), V, g.num_edges, splan)
        chunk_geometry = (nchunk, splan.chunk_size)
        if exchange_op is not None:
            exchange_quantized = (
                schedule.message_dtype == "int8"
                and exchange_op.collective == "psum"
                and jnp.issubdtype(dtype, jnp.floating))
            reduce_module = _emit_exchange(
                exchange_op, scan_chunks, splan.mesh,
                quantized=exchange_quantized, num_vertices=V)
            exchange_plane = "pull"
        else:
            def reduce_module(layouts, values, active):
                return scan_chunks(layouts["coo"], layouts["deg"], values,
                                   active)

    # ---- superstep = Receive/Reduce (module) + Apply + frontier ---------
    apply_fn = apply_op.fn
    frontier_dead = frontier_op.dead
    touched_free = fstep.touched_free if fstep is not None else False

    def make_superstep(module):
        @jax.jit
        def superstep(layouts, values, active):
            red, got = module(layouts, values, active)
            new = apply_fn(values, red)
            if frontier_dead:
                # frontier='all': every vertex stays active, no change mask
                return new, jnp.ones_like(active)
            if touched_free and frontier_op.mode == "changed":
                # fused stage with an identity-fixpoint apply: untouched
                # vertices hold the reduce identity, which the apply
                # fixes — `got` is never read, so its whole gather chain
                # is dead code XLA deletes (the touched-mask elision)
                changed = new != values
                return new, changed
            take = got if frontier_op.mode == "changed" else jnp.ones_like(got)
            new = jnp.where(take, new, values)
            changed = new != values
            next_active = changed if frontier_op.mode == "changed" \
                else jnp.ones_like(changed)
            return new, next_active
        return superstep

    # ---- pull plane: bitmap block-skipping sweep when the fusion pass
    # legalized it, otherwise the (structurally fused) dense sweep -------
    pull_sweep = fstep.pull_sweep if fstep is not None else "dense"
    pull_block_tiers = None
    pull_blocks_total = None
    if pull_sweep == "bitmap":
        fe_pull = lay.forward_ell(schedule.push_ell_width)
        host["fwd"] = _fwd_device_arrays(fe_pull)
        superstep, pull_block_tiers = _emit_pull_bitmap(
            ir, fstep, pplan, fe_pull, reduce_module, sub_sweep,
            use_pallas, g.num_edges, schedule)
        pull_blocks_total = pplan.num_blocks
    else:
        superstep = _wrap_superstep_stats(make_superstep(reduce_module),
                                          g.num_edges)

    # ---- push direction: emit the twin superstep when legal + wanted ----
    push_superstep = None
    push_layout = None
    push_tiers = None
    push_rf_fn = None
    push_stat_pes = 1
    push_pe_rows = None
    push_pe_edges = None
    if push_op is not None:
        push_layout = push_op.layout
        if push_op.layout == "fwd_ell":
            fe = lay.forward_ell(schedule.push_ell_width)
            if exchange_op is not None and splan.mesh is not None \
                    and fe.num_rows >= 1:
                # multi-PE: shard the push plane over forward-ELL intervals
                sfe = lay.forward_ell_shards(schedule.push_ell_width, pes)
                host["fwd_pe"] = _fwd_pe_device_arrays(sfe)
                push_superstep, push_tiers, push_rf_fn = \
                    _emit_push_ell_sharded(
                        ir, push_op, sfe, apply_fn, reduce_module,
                        use_pallas, splan.mesh, exchange_op)
                push_stat_pes = pes
                push_pe_rows = sfe.rows_per_pe
                push_pe_edges = sfe.edges_per_pe
                exchange_plane = "push"
            else:
                host["fwd"] = _fwd_device_arrays(fe)
                push_superstep, push_tiers, push_rf_fn = _emit_push_ell(
                    ir, push_op, fe, apply_fn, reduce_module, use_pallas)
        else:
            scatter, host["push_coo"] = _emit_push_scatter(ir, push_op, g,
                                                           splan)
            push_superstep = make_superstep(scatter)
    if fused.kernel == "edge_block" and exchange_plane != "push":
        # a dense plan whose push plane didn't shard (edgeless forward
        # ELL, or no push twin) runs fully replicated — report pes=1
        pes = 1

    # the default init values are program-static: materialized once at
    # staging time (layouts["init"]) with the rooted form staged (the
    # eager per-run scatter chain cost ~1 ms of every run() call)
    @jax.jit
    def _rooted(values, roots):
        root_val = jnp.asarray(0, dtype)
        values = values.at[roots].set(root_val)
        active = jnp.zeros((V,), bool).at[roots].set(True)
        return values, active

    def init_state(layouts, roots=None, values=None):
        if values is None:
            values = layouts["init"]
        if roots is not None:
            return _rooted(values, jnp.asarray(roots))
        return values, jnp.ones((V,), bool)

    layouts = _place_layouts(host, comm, splan.mesh)

    emit_s = time.perf_counter() - t_emit0
    preprocess_s = sum(lay.build_times_s.values()) - pre_before
    return {
        "layouts": lay,
        "device_layouts": layouts,
        "superstep": superstep,
        "push_superstep": push_superstep,
        "init_state": init_state,
        "push_tiers": push_tiers,
        "push_layout": push_layout,
        "push_rf_fn": push_rf_fn,
        "push_stat_pes": push_stat_pes,
        "push_pe_rows": push_pe_rows,
        "push_pe_edges": push_pe_edges,
        "pes": pes,
        "exchange_plane": exchange_plane,
        "exchange_quantized": exchange_quantized,
        "chunk_geometry": chunk_geometry,
        "pull_sweep": pull_sweep,
        "pull_block_tiers": pull_block_tiers,
        "pull_blocks_total": pull_blocks_total,
        "loop_cache": {},
        "aot_done": False,
        "preprocess_s": preprocess_s,
        # every layout this program needed came from the graph-keyed
        # cache: 0.0 preprocess seconds means "reused", not "instant"
        "preprocess_cached": preprocess_s == 0.0
        and bool(lay.build_times_s),
        "emit_s": emit_s - preprocess_s,
    }
