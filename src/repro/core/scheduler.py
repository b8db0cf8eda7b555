"""Runtime scheduler (paper §V-C2): ``pipelines × PEs`` parallelism knobs.

The paper's FPGA scheduler picks how many hardware pipelines stream edge
blocks and how many replicated processing elements (PEs) run them. The
TPU-native analogues:

* **pipelines** → how many edge chunks are streamed per superstep
  (``lax.scan`` over chunks bounds the live working set, the VMEM/BRAM
  analogue) and the Pallas grid size inside the dense kernel.
* **PEs** → mesh shards: each PE owns an edge partition (``shard_map`` over
  the ``pe`` axis) and combines vertex updates with ``psum``-style
  collectives chosen by the reduce op.

The scheduler also owns the runtime **direction policy**
(:class:`DirectionPolicy`): the paper's scheduler picks the right hardware
module per phase, and the direction-optimizing engine extends that choice
to the *edge-processing direction* per superstep — pull (every vertex
gathers over in-edges, O(E) work) vs push (only frontier vertices scatter
over out-edges, O(Σ out_deg(frontier)) work), with Beamer-style
alpha/beta switching on frontier occupancy in ``'auto'`` mode.

``plan_for_devices`` is the elastic-scaling hook: given a degraded device
count (node failure), it re-plans the same program onto fewer PEs — the
paper's "flexible parallelism" applied to fault tolerance.
"""
from __future__ import annotations

import dataclasses
import math

import jax

from ..errors import DeviceCountError
from ._jax_compat import make_mesh


@dataclasses.dataclass(frozen=True)
class DirectionPolicy:
    """Runtime direction optimization knobs (Beamer-style alpha/beta).

    ``mode='pull'`` / ``'push'`` pin every superstep to one direction;
    ``'auto'`` lets the compiled program switch per superstep on frontier
    occupancy, with hysteresis:

    * while pushing, stay push while ``m_f < E / alpha`` (``m_f`` = edges
      out of the frontier) — a growing frontier flips to pull;
    * while pulling, re-enter push when ``n_f < V / beta`` (``n_f`` =
      frontier vertex count) — a draining frontier flips back.

    The hysteresis structure is Beamer's; the default thresholds are
    recalibrated to the frontier-compacted push engine
    (``kernels/push_ell.py``).  The previous chunk-scanned scatter paid a
    ~5-8× per-edge penalty over the dense pull stream, so wall-clock
    tuning meant raising alpha well above the traversal model.  The
    compacted engine removed that asymmetry: a push superstep costs
    ``O(R + capacity·width)`` when a capacity tier covers the live rows
    (``r_f``, see :func:`push_capacity_tiers`) and one O(E) full push
    otherwise — one gather per row and one scatter per slot, which on a
    v5e undercuts the dense pull sweep's one gather per slot.  Push is
    therefore never meaningfully slower than pull, and the thresholds
    revert to the pure traversal model — the paper's hardware cost, where
    an FPGA frontier FIFO streams only live edges and
    ``report.run_stats['edges_traversed']`` counts ``m_f`` per push
    superstep:

    * ``alpha=1.0`` — push pays (in traversals) exactly while the
      frontier's out-edges are fewer than E;
    * ``beta=4.0`` — enter push as soon as the frontier drains below V/4
      (entry risk is bounded by the full push, so entering early is
      cheap; all-active starts like WCC begin pull and flip to push as
      labels converge).

    ``benchmarks/direction.py`` re-derives this calibration from per-edge
    costs measured on XLA:CPU (pull stream vs compacted push vs fallback)
    and records them in ``BENCH_graph.json``'s crossover section.  Push mode
    additionally requires the program to pass the translator's
    direction-legality analysis; illegal programs run pull regardless.

    The policy applies unchanged across PE counts: under a multi-PE plan
    the push superstep runs the sharded forward-ELL engine (per-PE row
    intervals + reduce-matched collective), and the direction register is
    computed on the replicated frontier — equal to the psum of per-PE
    partial occupancy counts — so every PE takes the same direction each
    superstep.
    """

    mode: str = "auto"           # 'pull' | 'push' | 'auto'
    alpha: float = 1.0           # push→pull when m_f > E/alpha
    beta: float = 4.0            # pull→push when n_f < V/beta

    def __post_init__(self):
        if self.mode not in ("pull", "push", "auto"):
            raise ValueError(f"unsupported direction mode: {self.mode}")
        if self.alpha <= 0 or self.beta <= 0:
            raise ValueError("alpha and beta must be > 0")

    def describe(self) -> str:
        """One-line summary for reports and IR dumps."""
        return f"{self.mode}(alpha={self.alpha:g}, beta={self.beta:g})"


@dataclasses.dataclass(frozen=True)
class AdmissionPolicy:
    """Serving-plane admission knobs (paper §V-C2's runtime scheduler,
    lifted from per-superstep direction choice to per-query batching).

    The serving plane (:mod:`repro.serve.graph_serve`) keeps one fixed
    ``slots``-lane batch per compiled program and continuously admits
    queued queries into converged lanes between slices of
    ``slice_supersteps`` supersteps — the graph analogue of continuous
    batching over a decode slot pool (``serve/decode.py``).

    * ``slots`` — lanes per batched program (the vmap width).  More slots
      raise throughput on bursty streams but each slice pays every lane's
      superstep cost (vmap executes frozen lanes as selects).
    * ``slice_supersteps`` — supersteps per slice between admission
      points.  Smaller slices free converged lanes sooner (lower queue
      latency) at the cost of more host round-trips; convergence is
      checked once per slice, never mid-slice.
    * ``max_queue`` — queue bound; ``submit`` raises when full (0 =
      unbounded).  Back-pressure, not silent drops.
    * ``coalesce`` — identical in-flight queries (same program identity,
      same root) share one lane and one answer instead of burning a slot
      each.
    """

    slots: int = 8               # lanes per batched program
    slice_supersteps: int = 4    # supersteps between admission points
    max_queue: int = 0           # submit() bound; 0 = unbounded
    coalesce: bool = True        # duplicate queries share a lane

    def __post_init__(self):
        if self.slots < 1:
            raise ValueError("slots must be >= 1")
        if self.slice_supersteps < 1:
            raise ValueError("slice_supersteps must be >= 1")
        if self.max_queue < 0:
            raise ValueError("max_queue must be >= 0 (0 = unbounded)")

    def describe(self) -> str:
        """One-line summary for reports and benchmark payloads."""
        q = self.max_queue or "unbounded"
        return (f"slots={self.slots} slice={self.slice_supersteps} "
                f"queue={q} coalesce={self.coalesce}")


@dataclasses.dataclass(frozen=True)
class ScheduleConfig:
    """Paper Algorithm 1, line 5: ``Set Pipeline = 8, PE = 1``."""

    pipelines: int = 8           # edge-stream chunks per superstep
    pes: int = 1                 # processing elements = mesh shards
    backend: str = "auto"        # 'auto' | 'dense' | 'sparse'
    block_rows: int = 128        # Pallas tile rows (dense backend)
    message_dtype: str | None = None   # e.g. 'int8' → comm quantization
    direction: DirectionPolicy = DirectionPolicy()  # push/pull/auto policy
    push_ell_width: int = 8      # forward-ELL row width (compacted push)
    # bitmap-frontier pull plane: 'auto' engages the block-skipping sweep
    # when the superstep-fusion pass proves it legal AND the backend's
    # cost model favors it — currently the Pallas path only, where the
    # in-kernel early-out skips real per-block work; on the XLA path the
    # flat dense sweep is measured faster than the skip bookkeeping, so
    # 'auto' resolves dense there (IR note records it).  'bitmap' forces
    # the block-skipping sweep on any backend (tests, benchmarks, A/B);
    # 'dense' pins the flat full sweep.  All choices are bit-exact.
    pull_sweep: str = "auto"     # 'auto' | 'bitmap' | 'dense'
    # edge slots per skippable pull block (multiple of 8).  Small blocks
    # are what make skipping real on scattered power-law frontiers:
    # measured block liveness only tracks row liveness below ~8 rows per
    # block, and the flat width-8 view keeps per-block bookkeeping cheap
    pull_block_slots: int = 64
    # out-of-core partitioned execution: split the edge set into
    # ``partitions`` contiguous source-vertex intervals and stream each
    # partition's layouts host→device per superstep (double-buffered),
    # skipping partitions whose interval holds no live source vertex.
    # ``partition_budget_bytes`` instead derives the partition count from
    # a device-memory budget for the streamed edge arrays
    # (:func:`estimate_stream_bytes`); when both are set the larger
    # resolved count wins.  ``partitions == 1`` (and no budget) keeps the
    # resident engine.
    partitions: int = 1
    partition_budget_bytes: int | None = None
    # fault tolerance: hard superstep budget staged into the run loop's
    # cond.  ``None`` resolves to V+1 (the diameter bound — no simple
    # traversal needs more), so an adversarial non-converging program
    # terminates with partial values and ``terminated='budget'`` in
    # ``run_stats`` instead of hanging the device loop.  A program's own
    # ``max_iters`` still applies; the effective budget is the smaller.
    max_supersteps: int | None = None
    # opt-in NaN probe between supersteps (float-valued programs): when a
    # superstep produces a NaN the frontier is frozen, the loop exits,
    # and ``run_stats['terminated']`` reads 'diverged'.  NaN-only on
    # purpose — +/-inf are reduce identities (SSSP's unreached vertices
    # stay +inf), not divergence.  Off by default — the probe costs an
    # O(V) reduction per superstep.
    probe_divergence: bool = False

    def __post_init__(self):
        if self.backend not in ("auto", "dense", "sparse"):
            raise ValueError(self.backend)
        if self.pipelines < 1 or self.pes < 1:
            raise ValueError("pipelines and pes must be >= 1")
        if self.push_ell_width < 1:
            raise ValueError("push_ell_width must be >= 1")
        if self.pull_sweep not in ("auto", "bitmap", "dense"):
            raise ValueError(f"unsupported pull_sweep: {self.pull_sweep}")
        if self.pull_block_slots < 8 or self.pull_block_slots % 8:
            raise ValueError("pull_block_slots must be a positive "
                             "multiple of 8")
        if not isinstance(self.direction, DirectionPolicy):
            raise TypeError("direction must be a DirectionPolicy")
        if self.partitions < 1:
            raise ValueError("partitions must be >= 1")
        if self.partition_budget_bytes is not None \
                and self.partition_budget_bytes < 1:
            raise ValueError("partition_budget_bytes must be >= 1 (or None)")
        if self.max_supersteps is not None and self.max_supersteps < 1:
            raise ValueError("max_supersteps must be >= 1 (or None)")

    def superstep_budget(self, program_max_iters: int | None,
                         num_vertices: int) -> int:
        """Effective superstep bound for the staged ``while_loop`` cond.

        The smaller of the program's own ``max_iters`` (fixed-iteration
        programs like PageRank) and this schedule's ``max_supersteps``;
        the latter defaults to ``V + 1``, the diameter bound — a budget a
        converging traversal can never hit, so the knob only bites on
        runaway programs.
        """
        cap = (self.max_supersteps if self.max_supersteps is not None
               else num_vertices + 1)
        if program_max_iters is not None:
            cap = min(cap, program_max_iters)
        return max(1, cap)


@dataclasses.dataclass(frozen=True)
class SchedulePlan:
    """Resolved schedule: concrete chunking + mesh for this graph/devices.

    The plan owns *all* chunk/PE arithmetic: ``num_chunks`` is already
    rounded up to a multiple of the resolved PE count (so per-PE chunk
    slices are equal-sized) and ``chunk_size`` is derived from the rounded
    count — the translator stages exactly these numbers, so
    :meth:`describe` and the backend-selection pass dump always agree with
    the staged chunk arrays.
    """

    config: ScheduleConfig
    backend: str                 # resolved ('dense' | 'sparse')
    num_chunks: int              # edge-stream chunks (>=1, multiple of pes)
    chunk_size: int              # edges per chunk (padded, >=1)
    mesh: jax.sharding.Mesh | None   # None → single device
    direction: DirectionPolicy = DirectionPolicy()  # carried from config
    # out-of-core axis: resolved interval-partition count (1 = resident)
    # and the byte budget that resolved it (None when count-pinned).  The
    # plan owns this arithmetic like it owns chunks/PEs — the translator
    # dispatches to the partitioned engine iff ``num_partitions > 1``.
    num_partitions: int = 1
    partition_budget_bytes: int | None = None

    @property
    def pes(self) -> int:
        """Resolved PE count (mesh size; 1 when running un-sharded)."""
        return 1 if self.mesh is None else int(self.mesh.devices.size)

    def describe(self) -> str:
        """One-line summary for IR/pass dumps (backend-selection pass)."""
        parts = ""
        if self.num_partitions > 1:
            budget = self.partition_budget_bytes
            parts = (f" partitions={self.num_partitions}"
                     + (f"(budget={budget}B)" if budget else ""))
        return (f"backend={self.backend} pipelines={self.num_chunks} "
                f"chunk_size={self.chunk_size} pes={self.pes} "
                f"direction={self.direction.describe()} "
                f"pull_sweep={self.config.pull_sweep}{parts}")


def push_capacity_tiers(num_rows: int) -> tuple[int, int]:
    """Compaction capacity tiers for the forward-ELL push engine.

    The compacted kernel's cost is proportional to its *capacity* (every
    buffer slot is gathered and scattered, live or not), so one capacity
    sized for the widest frontier would erase the savings on sparse ones.
    Two power-of-two tiers — ``~R/64`` and ``~R/16`` rows — let the
    runtime pick the smallest tier covering the live row count ``r_f``
    each superstep.  Every compacted superstep also pays the O(R)
    live-row gather ``active[row_src]`` (~20 ns/row on a v5e), so beyond
    the large tier the push superstep takes the *full push* instead
    (``push_ell.full_push_reduce``): every row in chunks of the large
    tier's capacity, no compaction.  Per element on a v5e (TPU traces of
    Graph500 BFS at scale 22 and SSSP at scale 21): the segment-combine
    scatter ~6-7 ns per slot, a vertex-table gather ~20 ns per row
    (~2.5 ns per slot at width 8), the dense pull sweep's gather it
    replaced ~10-19 ns per slot.  Derived from the forward-ELL row count
    so the tiers track graph shape, not raw E.

    Under a multi-PE plan the translator passes the *largest interval's*
    row count (``ShardedForwardELL.rows_per_pe_max``): ``shard_map``
    traces one SPMD program, so the tier shapes are shared mesh-wide,
    but each PE still switches on its own local ``r_f`` — the per-PE
    capacity tiers of the sharded engine.
    """
    def p2floor(x: int) -> int:
        return 1 << max(x.bit_length() - 1, 0)

    small = max(256, p2floor(max(num_rows, 1) // 64))
    large = max(2 * small, p2floor(max(num_rows, 1) // 16))
    return small, large


# Live-block capacity fractions of the bitmap pull plane: tier t covers a
# superstep whose frontier out-edge count fits
# ``ceil(num_blocks · PULL_BLOCK_TIERS[t])``; wider frontiers take the
# dense full sweep.  Two tiers mirror the push engine's small/large
# capacity split — compacted cost is proportional to *capacity* (the
# touched pre-pass scatter and the block gather both pay per buffer slot,
# live or not), so one wide tier would erase the savings on near-empty
# frontiers.  The fractions are deliberately tight: the flat dense sweep
# costs ~2.5 ns/edge while the pre-pass byte-scatter costs ~60 ns/slot, so
# a compacted superstep only wins when its capacity is a small fraction of
# the block count — beyond ~1/16 it merely matches the dense sweep it
# replaces (measured on the 50k/500k R-MAT; see BENCH_graph.json).
PULL_BLOCK_TIERS = (1 / 64, 1 / 16)


def pull_block_capacities(num_blocks: int) -> tuple:
    """Per-tier live-block capacities for the compacted pull sweep:
    ``caps[t] = max(1, ceil(num_blocks * PULL_BLOCK_TIERS[t]))``.

    Derived from the flat view's block count so the tiers track graph
    shape (like :func:`push_capacity_tiers` tracks forward-ELL rows).
    """
    return tuple(max(1, math.ceil(num_blocks * f))
                 for f in PULL_BLOCK_TIERS)


# Streamed bytes per edge of one partition plane (width-8 ELL view): 8
# bytes of dst+weight slots x ~1.3 measured padding on power-law graphs,
# plus the per-row owner id (4 bytes / width slots).  Deliberately a planning
# *estimate* (the real per-partition layouts aren't built yet at plan time);
# the partitioned engine's store reports exact resident bytes at run time.
STREAM_BYTES_PER_EDGE = 11


def estimate_stream_bytes(num_edges: int, width: int = 8) -> int:
    """Planning estimate of one streamed plane's total edge-array bytes.

    What :func:`plan` divides by ``partition_budget_bytes`` to resolve the
    interval-partition count: ``ceil(estimate / budget)`` partitions keep
    each partition's streamed pull (or push) arrays under the budget.
    """
    return int(num_edges * STREAM_BYTES_PER_EDGE)


def choose_backend(cfg: ScheduleConfig, *, num_vertices: int,
                   num_edges: int, avg_degree: float) -> str:
    """Module selection heuristic (paper: translator picks the module).

    Dense ELL blocks win when degree is moderate (padding bounded, regular
    streams); very sparse or hub-dominated graphs keep the sorted-CSR
    segment path.
    """
    if cfg.backend != "auto":
        return cfg.backend
    return "dense" if avg_degree >= 4.0 else "sparse"


def plan(cfg: ScheduleConfig, *, num_vertices: int, num_edges: int,
         devices: list | None = None,
         fixed_partitions: int | None = None) -> SchedulePlan:
    avg_degree = num_edges / max(num_vertices, 1)
    backend = choose_backend(cfg, num_vertices=num_vertices,
                             num_edges=num_edges, avg_degree=avg_degree)
    # ---- out-of-core axis: resolve the interval-partition count ---------
    # ``fixed_partitions`` pins it (a pre-partitioned .npz container's
    # physical cut count is an input to planning, not something the budget
    # can re-derive); otherwise the explicit count and the budget-derived
    # count compete and the larger wins.  Clamped to [1, V] — intervals
    # cut on vertices.
    if fixed_partitions is not None:
        num_partitions = max(1, int(fixed_partitions))
    else:
        num_partitions = max(1, cfg.partitions)
        if cfg.partition_budget_bytes is not None:
            est = estimate_stream_bytes(num_edges, cfg.push_ell_width)
            num_partitions = max(
                num_partitions,
                -(-est // cfg.partition_budget_bytes))
    num_partitions = min(num_partitions, max(num_vertices, 1))
    if num_partitions > 1 and cfg.pes > 1:
        raise ValueError(
            "partitioned execution is single-PE: the streamed partitions "
            f"already tile the edge set (got partitions={num_partitions}, "
            f"pes={cfg.pes})")
    mesh = None
    pes = 1
    if cfg.pes > 1:
        devices = devices if devices is not None else jax.devices()
        if len(devices) < cfg.pes:
            raise DeviceCountError(
                f"schedule asks for pes={cfg.pes} but only {len(devices)} "
                "device(s) are available; use plan_for_devices to re-plan "
                "onto a smaller pool", requested=cfg.pes,
                available=len(devices))
        pes = cfg.pes
        mesh = make_mesh((pes,), ("pe",), devices=devices[:pes])
    # Chunk geometry is final here — the translator must not re-derive it.
    # Round the chunk count up to a multiple of the resolved PE count so
    # each PE owns an equal-sized chunk slice, and keep chunk_size >= 1 so
    # an edgeless graph still stages well-formed (all-PAD) chunk arrays.
    num_chunks = max(1, min(cfg.pipelines, math.ceil(num_edges / 1024)))
    num_chunks = -(-num_chunks // pes) * pes
    chunk_size = max(1, math.ceil(num_edges / num_chunks))
    return SchedulePlan(config=cfg, backend=backend, num_chunks=num_chunks,
                        chunk_size=chunk_size, mesh=mesh,
                        direction=cfg.direction,
                        num_partitions=num_partitions,
                        partition_budget_bytes=cfg.partition_budget_bytes)


def plan_for_devices(cfg: ScheduleConfig, num_devices: int,
                     **graph_meta) -> SchedulePlan:
    """Elastic re-planning hook: same program, degraded device pool.

    The explicit degrade path: ``pes`` is clamped to ``num_devices`` and
    to ``jax.devices()``, where :func:`plan` itself refuses a ``pes`` the
    pool cannot hold.
    """
    pes = min(cfg.pes, max(1, num_devices), len(jax.devices()))
    return plan(dataclasses.replace(cfg, pes=pes), **graph_meta)
