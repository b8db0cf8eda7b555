"""Communication manager (paper §V-C1).

The paper's manager wraps XRT/XOCL: it moves graph data over PCIe, tracks
accelerator status, and exposes configuration. On TPU the host↔device and
device↔device planes are JAX shardings and collectives, so the manager here:

* plans and executes **placement** (``device_put`` with ``NamedSharding``) —
  the DMA-descriptor analogue;
* tracks **transfer statistics** (bytes host→device, per-superstep collective
  bytes) for the cost reports;
* provides optional **message quantization** (int8) for cross-PE vertex-value
  combines — the graph-engine analogue of gradient compression;
* exposes **status** (device kind, memory per device, live buffers).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from . import faults


@dataclasses.dataclass
class TransferStats:
    """Transfer accounting: placement bytes plus the cross-PE exchange plane.

    ``collective_bytes_per_superstep`` is the *estimate* for one combine
    (set by :meth:`CommManager.estimate_collective_bytes`, never summed);
    ``collective_supersteps`` / ``collective_bytes_total`` are the
    accumulated per-run totals recorded by the compiled program's run loop
    (:meth:`record_collective`) — the exchanges that actually executed,
    not a static estimate.
    """

    host_to_device_bytes: int = 0
    device_to_host_bytes: int = 0
    collective_bytes_per_superstep: int = 0
    # frontier plane of the sharded pull sweep: bytes one touched-mask
    # combine ships per participant (packed bitmap words or int8 table —
    # see CommManager.estimate_frontier_bytes); folded into the run loop's
    # executed exchange accounting alongside the value-table bytes
    frontier_bytes_per_superstep: int = 0
    collective_supersteps: int = 0
    collective_bytes_total: int = 0
    placements: int = 0
    # out-of-core partition streaming plane (repro.core.stream): bytes the
    # double-buffered interval stream actually shipped host→device, how
    # many partition sweeps that covered, and how many sweeps the bitmap
    # frontier skipped *before* any transfer.  The wall/prefetch/compute
    # triple measures transfer↔compute overlap: with perfect overlap
    # wall ≈ max(prefetch, compute); with none, wall ≈ prefetch + compute.
    partition_bytes_h2d: int = 0
    partitions_transferred: int = 0
    partitions_skipped: int = 0
    partition_prefetch_s: float = 0.0
    partition_compute_s: float = 0.0
    partition_wall_s: float = 0.0
    # fault-tolerance counters (repro.core.stream retry path): transient
    # fetch/transfer failures that were retried with backoff, and
    # checksum mismatches that forced an evict + re-read from the
    # container.  Non-zero corruptions with a completed run means the
    # stream *recovered* — the answer is still bit-exact.
    partition_retries: int = 0
    partition_corruptions: int = 0

    def record_h2d(self, nbytes: int):
        self.host_to_device_bytes += int(nbytes)
        self.placements += 1

    def record_d2h(self, nbytes: int):
        self.device_to_host_bytes += int(nbytes)

    def record_partition_h2d(self, nbytes: int, seconds: float = 0.0):
        """One partition's arrays entered the stream (device_put issued)."""
        self.partition_bytes_h2d += int(nbytes)
        self.partitions_transferred += 1
        self.partition_prefetch_s += float(seconds)

    def record_partition_skip(self, n: int = 1):
        """Partition sweeps the frontier summary killed before transfer."""
        self.partitions_skipped += int(n)

    def record_partition_superstep(self, wall_s: float, compute_s: float):
        """One streamed superstep's wall clock and pure-compute time."""
        self.partition_wall_s += float(wall_s)
        self.partition_compute_s += float(compute_s)

    def overlap_efficiency(self) -> float:
        """Measured transfer/compute overlap of the partition stream, 0..1.

        ``(prefetch + compute − wall) / min(prefetch, compute)``: the
        fraction of the *overlappable* time (the shorter of the two
        phases) actually hidden.  1.0 = wall is max(phases); 0.0 = the
        phases fully serialized (or nothing streamed).
        """
        shorter = min(self.partition_prefetch_s, self.partition_compute_s)
        if shorter <= 0.0 or self.partition_wall_s <= 0.0:
            return 0.0
        hidden = (self.partition_prefetch_s + self.partition_compute_s
                  - self.partition_wall_s)
        return float(np.clip(hidden / shorter, 0.0, 1.0))

    def record_partition_retry(self, n: int = 1):
        """A transient partition fetch/transfer failure was retried."""
        self.partition_retries += int(n)

    def record_partition_corruption(self, n: int = 1):
        """A checksum mismatch forced an evict + re-read of a partition."""
        self.partition_corruptions += int(n)

    def record_collective(self, nbytes_per_superstep: int, supersteps: int):
        """Accumulate one run's executed exchanges (run-loop wiring).

        Only the totals accumulate here; ``collective_bytes_per_superstep``
        stays what :meth:`CommManager.estimate_collective_bytes` set (a
        batched run's per-superstep volume is batch-multiplied and would
        silently redefine that documented field).

        This is also the ``comm.collective`` fault-injection point: the
        registry hook fires when a run records an executed exchange, so a
        chaos test can prove a poisoned collective surfaces as a typed
        error instead of a silent wrong answer.
        """
        faults.trip("comm.collective")
        self.collective_supersteps += int(supersteps)
        self.collective_bytes_total += int(nbytes_per_superstep) * int(supersteps)


def _tree_nbytes(tree: Any) -> int:
    return sum(np.prod(x.shape) * x.dtype.itemsize
               for x in jax.tree.leaves(tree) if hasattr(x, "shape"))


class CommManager:
    """Paper: 'control shell' — here a placement/transfer planner."""

    def __init__(self, mesh: jax.sharding.Mesh | None = None):
        self.mesh = mesh
        self.stats = TransferStats()

    # -- status (paper: xbutil / XRT status queries) ------------------------
    def status(self) -> dict:
        devs = list(self.mesh.devices.flat) if self.mesh else jax.devices()
        return {
            "num_devices": len(devs),
            "platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "mesh": None if self.mesh is None else dict(
                shape=dict(zip(self.mesh.axis_names, self.mesh.devices.shape))),
        }

    # -- placement (paper: Transport(CPU_ip, FPGA_ip, GraphCSC)) -----------
    def transport(self, tree: Any,
                  spec: jax.sharding.PartitionSpec | None = None, *,
                  mesh: jax.sharding.Mesh | None = None) -> Any:
        """Place a pytree on the device/mesh; records transfer bytes.

        ``mesh`` overrides the manager's own (the translator places a
        plan's layouts on the plan's ``pe`` mesh).
        """
        self.stats.record_h2d(_tree_nbytes(tree))
        mesh = mesh if mesh is not None else self.mesh
        if mesh is None or spec is None:
            return jax.device_put(tree)
        sharding = jax.sharding.NamedSharding(mesh, spec)
        return jax.device_put(tree, sharding)

    def fetch(self, tree: Any) -> Any:
        """Device → host (paper: result read-back over PCIe)."""
        self.stats.record_d2h(_tree_nbytes(tree))
        return jax.device_get(tree)

    # -- message quantization (cross-PE combine compression) ---------------
    @staticmethod
    def quantize_messages(x: jax.Array) -> tuple[jax.Array, jax.Array]:
        """Symmetric int8 quantization of vertex messages."""
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-8) / 127.0
        q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
        return q, scale

    @staticmethod
    def dequantize_messages(q: jax.Array, scale: jax.Array,
                            dtype=jnp.float32) -> jax.Array:
        return q.astype(dtype) * scale

    @staticmethod
    def quantized_psum(x: jax.Array, axis_name: str, *, pes: int) -> jax.Array:
        """Cross-PE sum of per-PE partials with an int8 wire format.

        The per-superstep exchange of the sharded pull plane when
        ``ScheduleConfig.message_dtype == 'int8'``: a quantized *ring
        all-reduce* built from two collectives whose payloads are both
        int8 (a plain ``psum`` would widen the operand and ship full
        precision, defeating the point):

        1. **scale agreement** — ``pmax`` of the local abs-max (two such
           scalar agreements per combine, itemized by
           :meth:`estimate_collective_bytes`); every PE quantizes its
           partial table with the shared symmetric
           ``scale = max|x|/127``;
        2. **reduce-scatter phase** — ``all_to_all`` deals each PE its
           1/p chunk of every peer's int8 table (``(p−1)/p·V`` bytes per
           participant); each PE sums its chunk exactly in int32 (sums
           of p int8 values cannot overflow it) and *re-quantizes* the
           chunk sum onto an adaptive grid ``scale₂ = max|Σq|/127``
           (second pmax — chunk sums span ``±127·p``, but real ones are
           far smaller, and the adaptive step keeps the combined table
           at full int8 resolution instead of a fixed ``p×``-coarser
           grid, which measurably compounds in iterative algorithms);
        3. **all-gather phase** — ``all_gather`` of the int8 chunk
           results (another ``(p−1)/p·V`` bytes), then one dequantize:
           ``q₂·scale₂·scale``.

        Total wire: ``2·(p−1)/p·V`` at 1 byte/element — the classic ring
        all-reduce volume at int8 width, a genuine ``itemsize×`` saving
        at *any* PE count (a gather-of-full-tables design would ship
        ``(p−1)·V`` and lose its advantage by p=8).  Error per element:
        ≤ ``pes·scale/2`` from the initial quantization plus
        ≤ ``scale₂·scale/2 ≤ pes·scale/2`` from the re-quantization —
        ``pes·scale`` worst-case, typically far tighter thanks to the
        adaptive grid.

        Only ever applied to *float add* combines: min/max and integer
        add reduces stay on the full-precision collective so those
        programs remain bit-exact (the escape hatch the translator's
        exchange emitter enforces).  ``pes`` must be the ``axis_name``
        mesh axis size (static: the chunking needs it at trace time).
        """
        v = x.shape[0]
        absmax = jax.lax.pmax(jnp.max(jnp.abs(x)), axis_name)
        scale = jnp.maximum(absmax, 1e-8) / 127.0
        q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
        qp = jnp.pad(q, (0, (-v) % pes)).reshape(pes, -1)   # (p, V/p) int8
        # reduce-scatter: PE i receives every peer's chunk i (int8 wire)
        recv = jax.lax.all_to_all(qp, axis_name, split_axis=0, concat_axis=0)
        chunk_sum = jnp.sum(recv.astype(jnp.int32), axis=0)  # exact, local
        # adaptive re-quantization grid (in units of `scale`)
        scale2 = jnp.maximum(
            jax.lax.pmax(jnp.max(jnp.abs(chunk_sum)), axis_name), 1
        ).astype(jnp.float32) / 127.0
        q2 = jnp.clip(jnp.round(chunk_sum / scale2), -127, 127) \
            .astype(jnp.int8)
        # all-gather the int8 chunk results back to a full table
        full = jax.lax.all_gather(q2, axis_name).reshape(-1)[:v]
        return full.astype(x.dtype) * (scale2 * scale)

    @staticmethod
    def bitmap_or(words: jax.Array, axis_name: str, *, pes: int) -> jax.Array:
        """Cross-PE OR of packed frontier bitmaps (the mask exchange).

        The sharded pull plane must combine each PE's touched mask; the
        old wire format was an int8 table through ``pmax`` (a ring
        all-reduce, ``2·(p−1)/p·V`` bytes per participant).  Packed uint32
        words cannot ride ``pmax`` (``max`` of words is not bitwise OR),
        so the bitmap ships as an ``all_gather`` of the V/32-word tables —
        ``(p−1)·V/8`` bytes received per participant — followed by a local
        OR fold (``pes`` is static, the fold unrolls).  That beats the
        int8 ring whenever ``(p−1)/8 < 2·(p−1)/p`` ⇔ ``p < 16``: 8× less
        wire at p=2, ~2× at p=8, break-even at p=16 — the translator keeps
        the int8 ``pmax`` form at p ≥ 16 (see
        :func:`repro.core.translator._emit_exchange`).  Bit-exact: OR of
        packed words equals ``pmax`` of the unpacked mask.
        """
        parts = jax.lax.all_gather(words, axis_name)     # (p, V/32)
        out = parts[0]
        for i in range(1, pes):
            out = out | parts[i]
        return out

    def estimate_frontier_bytes(self, num_vertices: int, pes: int,
                                packed: bool = True) -> int:
        """Per-superstep mask-exchange volume per participant.

        ``packed`` → the bitmap ``all_gather`` form: ``(p−1) · ceil(V/32) ·
        4`` bytes received per participant; otherwise the int8 ``pmax``
        ring: ``2·(p−1)/p · V``.  Recorded on
        :attr:`TransferStats.frontier_bytes_per_superstep`; the run loop
        folds it into the executed exchange totals.
        """
        if pes <= 1:
            vol = 0
        elif packed:
            vol = (pes - 1) * (-(-num_vertices // 32)) * 4
        else:
            vol = int(2 * (pes - 1) / pes * num_vertices)
        self.stats.frontier_bytes_per_superstep = vol
        return vol

    def estimate_collective_bytes(self, num_vertices: int, value_dtype,
                                  pes: int, quantized: bool = False) -> int:
        """Per-superstep cross-PE combine volume, per participant.

        A ring all-reduce moves ``2·(p−1)/p`` of the buffer per
        participant — at ``itemsize`` bytes/element in full precision,
        at 1 byte/element when ``quantized`` (:meth:`quantized_psum`
        executes exactly that volume as an int8 all-to-all
        reduce-scatter phase plus an int8 all-gather phase, each
        ``(p−1)/p·V``), plus the scale agreements (two float32 scalars
        through the same ring).  Records the per-superstep figure
        on :class:`TransferStats`; the executed-run totals accumulate
        separately via :meth:`TransferStats.record_collective` from the
        run loop.
        """
        if pes <= 1:
            return 0
        itemsize = 1 if quantized else jnp.dtype(value_dtype).itemsize
        vol = int(2 * (pes - 1) / pes * num_vertices * itemsize)
        if quantized:
            # scale agreements: two float32 scalars all-reduced per combine
            vol += 2 * int(2 * (pes - 1) / pes
                           * jnp.dtype(jnp.float32).itemsize)
        self.stats.collective_bytes_per_superstep = vol
        return vol

    def report(self) -> dict:
        """Stats + status snapshot: per-superstep estimate *and* run totals."""
        return dataclasses.asdict(self.stats) | self.status() | {
            "overlap_efficiency": self.stats.overlap_efficiency()}
