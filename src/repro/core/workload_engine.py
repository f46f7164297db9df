"""Batched workload-evaluation engine — the architecture-layer fold as one
tensor computation.

core/engine.py batches the circuit layer (the NVSim tech x capacity x
organization sweep); this module batches the layer DeepNVM++ stacks on top
of it: folding workload memory traffic through tuned cache designs to get
runtime, dynamic/leakage/DRAM energy, and EDP (paper Figs. 3-10).  The
scalar path (``traffic.runtime`` / ``traffic.energy``, one call per
(workload, memory, capacity)) survives as the parity reference, pinned by
tests/test_workload_engine.py to a few ulps.

Representation: structure-of-arrays, padded.  Every scenario — one
``TrafficStats``, i.e. one (workload, batch, training) execution — packs
its ``AccessStream`` tuple into rows of four [scenario, stream] tensors
(``bytes_total``, ``is_write``, ``reuse_distance``, ``dram_visible``) with
a stream-count ``mask`` marking real entries (padding rows carry zero
bytes, infinite reuse distance, and a False mask, so they contribute
nothing to any fold).  Designs — (memory, capacity) points read from
``engine.DesignTable`` — pack into five [design] vectors.  One jitted
float64 kernel then evaluates the full cross product

    [scenario] x [design]  ->  runtime / energy / EDP tensors [s, d]

reproducing the scalar path's operation order exactly: the miss-curve
``dram_tx`` fold, the \"simple model\" runtime (compute + serialized L2 +
DRAM stall), and the dynamic/leakage/DRAM energy terms.

The platform is itself a batched axis: ``evaluate_platforms`` evaluates

    [platform] x [scenario] x [design]

in one kernel call (platform parameters are a [p, 4] runtime input, so
e.g. GTX_1080TI vs TPU_V5E share one trace), returning one
:class:`WorkloadTable` view per platform.  Platform-independent tensors
(L2 transactions, DRAM transactions, dynamic energy) are computed once
and shared across the views.

:class:`WorkloadTable` wraps the result tensors with the same vocabulary
the scalar API uses (``total_j``/``edp``/``EnergyReport``), and
``evaluate`` memoizes tables per (scenarios, designs, platforms) so the
iso-capacity, iso-area, and scaling analyses plus the benchmarks all share
one evaluation — the whole cross-layer pipeline becomes two composed
batched computations (circuit sweep, workload fold).
"""

from __future__ import annotations

import dataclasses
import functools
from collections.abc import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core import traffic
from repro.core.cachemodel import LINE_BYTES, CacheDesign
from repro.core.tech import Platform, GTX_1080TI
from repro.core.traffic import (
    ASSOC_EFFICIENCY,
    COMPUTE_EFFICIENCY,
    MISS_CURVE_P,
    EnergyReport,
    TrafficStats,
)
from repro.core.workloads import Workload

# Platform parameters consumed by the fold, in the order they are packed
# into the platform vector (a runtime input, so a different platform —
# e.g. TPU_V5E — does not recompile the kernel).
PLATFORM_FIELDS = ("peak_flops", "mem_serialization", "dram_bw",
                   "dram_energy_per_byte")

@functools.lru_cache(maxsize=None)
def stats_for(workload: Workload, batch: int, training: bool) -> TrafficStats:
    """Memoized ``traffic.build`` — scenarios are shared across analyses."""
    return traffic.build(workload, batch, training)


# ---------------------------------------------------------------------------
# Packing: AccessStreams -> padded SoA tensors, designs -> vectors
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True, eq=False)
class StreamBatch:
    """Padded structure-of-arrays pack of many scenarios' AccessStreams."""

    keys: tuple[tuple[str, int, bool], ...]  # (workload, batch, training)
    bytes_total: np.ndarray     # [s, k] float64, padded 0.0
    is_write: np.ndarray        # [s, k] bool,    padded False
    reuse_distance: np.ndarray  # [s, k] float64, padded inf
    dram_visible: np.ndarray    # [s, k] bool,    padded False
    mask: np.ndarray            # [s, k] bool — True on real streams
    macs: np.ndarray            # [s] float64


def pad_width(k: int) -> int:
    """Pack-width bucket: the next power of two >= k (minimum 8).

    The chunked sweep path pads each chunk to a bucket instead of its
    exact stream-count maximum, so chunks with nearby widths share one
    compiled fold kernel; relative padding waste stays < 2x while the
    number of distinct kernel shapes stays O(log max_k)."""
    if k < 1:
        raise ValueError("pad_width needs k >= 1")
    w = 8
    while w < k:
        w *= 2
    return w


# Scenario/design axis-bucket floors of the bucketed (service) fold path:
# requests below the floor share the floor's compiled shape, so tiny specs
# don't each pin their own trace.
S_BUCKET_FLOOR = 4
D_BUCKET_FLOOR = 4


def axis_bucket(n: int, floor: int = 1) -> int:
    """Batch-axis shape bucket: the next power of two >= max(n, floor).

    The ``pad_width`` idea generalized to the scenario and design axes —
    the bucketed fold pads every axis to its bucket, so the set of
    compiled kernel shapes stays O(log^3) over arbitrary request sizes
    (the property that makes ``warmup`` able to pre-trace them all)."""
    if n < 1:
        raise ValueError("axis_bucket needs n >= 1")
    w = max(1, floor)
    while w < n:
        w *= 2
    return w


def pack(stats_seq: Sequence[TrafficStats],
         width: int | None = None) -> StreamBatch:
    """Pack scenarios into padded [scenario, stream] tensors.

    ``width`` overrides the padded stream-axis size (default: the max
    stream count across *these* scenarios).  The sharded sweep path packs
    per chunk — so one outlier scenario (e.g. googlenet train, 645
    streams) widens only its own chunk, not every chunk of the sweep; a
    global pack pads every scenario row to the global max and is the
    memory blowup that makes mixed mega-specs OOM earlier than cell count
    alone predicts.  Padding rows carry zero bytes, infinite reuse
    distance, and a False mask, so any width gives the same fold result.
    """
    stats_seq = tuple(stats_seq)
    k = max(len(s.streams) for s in stats_seq)
    if width is not None:
        if width < k:
            raise ValueError(f"width {width} < max stream count {k}")
        k = width
    n = len(stats_seq)
    bytes_total = np.zeros((n, k), dtype=np.float64)
    is_write = np.zeros((n, k), dtype=bool)
    reuse = np.full((n, k), np.inf, dtype=np.float64)
    visible = np.zeros((n, k), dtype=bool)
    mask = np.zeros((n, k), dtype=bool)
    for i, stats in enumerate(stats_seq):
        a = stats._arrays
        m = len(stats.streams)
        bytes_total[i, :m] = a["bytes_total"]
        is_write[i, :m] = a["is_write"]
        reuse[i, :m] = a["reuse_distance"]
        visible[i, :m] = a["dram_visible"]
        mask[i, :m] = True
    return StreamBatch(
        keys=tuple((s.workload, s.batch, s.training) for s in stats_seq),
        bytes_total=bytes_total, is_write=is_write, reuse_distance=reuse,
        dram_visible=visible, mask=mask,
        macs=np.array([s.macs_per_batch for s in stats_seq],
                      dtype=np.float64),
    )


def _design_vectors(designs: Sequence[CacheDesign]) -> tuple[np.ndarray, ...]:
    def as_vec(field: str) -> np.ndarray:
        return np.array([getattr(d, field) for d in designs], dtype=np.float64)

    return (as_vec("read_latency_s"), as_vec("write_latency_s"),
            as_vec("read_energy_j"), as_vec("write_energy_j"),
            as_vec("leakage_w"), as_vec("capacity_bytes"))


def _platform_vector(platform: Platform) -> np.ndarray:
    return np.array([getattr(platform, f) for f in PLATFORM_FIELDS],
                    dtype=np.float64)


# ---------------------------------------------------------------------------
# The jitted fold
# ---------------------------------------------------------------------------


def _miss_tx(bytes_total, rd, visible, caps):
    """[s, c] DRAM transactions — TrafficStats.dram_tx's fold, batched.

    Each stream misses with probability (RD / (RD + C_eff))^MISS_CURVE_P
    (RD=inf always misses); only DRAM-visible streams count.
    """
    c_eff = caps * ASSOC_EFFICIENCY                       # [c]
    r = rd[:, None, :]                                    # [s, 1, k]
    ratio = r / (r + c_eff[None, :, None])
    miss_p = jnp.where(jnp.isinf(r), 1.0, ratio ** MISS_CURVE_P)
    tx = bytes_total[:, None, :] / LINE_BYTES * miss_p
    return jnp.where(visible[:, None, :], tx, 0.0).sum(axis=2)


_miss_tx_kernel = jax.jit(_miss_tx)


def _fold(bytes_total, is_write, rd, visible, mask, macs,
          rl, wl, re_, we_, leak, caps, pmat):
    """The full [platform] x [scenario] x [design] workload fold.

    Streams [s, k], designs [d], platforms [p, 4] -> platform-dependent
    metric tensors [p, s, d] plus platform-independent [s] / [s, d] ones.
    Every expression keeps the scalar traffic.runtime/energy operation
    order so float64 results match the Python reference to the last ulps.
    """
    peak_flops = pmat[:, 0][:, None, None]       # [p, 1, 1]
    serialization = pmat[:, 1][:, None, None]
    dram_bw = pmat[:, 2][:, None, None]
    dram_epb = pmat[:, 3][:, None, None]
    bt = jnp.where(mask, bytes_total, 0.0)
    read_tx = jnp.where(is_write, 0.0, bt).sum(axis=1) / LINE_BYTES   # [s]
    write_tx = jnp.where(is_write, bt, 0.0).sum(axis=1) / LINE_BYTES
    dram_tx = _miss_tx(bt, rd, visible & mask, caps)                  # [s, d]

    t_compute = macs[None, :, None] * 2.0 \
        / (peak_flops * COMPUTE_EFFICIENCY)                           # [p, s, 1]
    t_l2 = read_tx[:, None] * rl[None, :] + write_tx[:, None] * wl[None, :]
    runtime_nodram = t_compute + serialization * t_l2[None]           # [p, s, d]
    runtime = runtime_nodram + (dram_tx * LINE_BYTES)[None] / dram_bw

    return dict(
        l2_read_tx=read_tx,
        l2_write_tx=write_tx,
        dram_tx=dram_tx,
        runtime_s=runtime,
        runtime_nodram_s=runtime_nodram,
        dyn_read_j=read_tx[:, None] * re_[None, :],
        dyn_write_j=write_tx[:, None] * we_[None, :],
        leak_j=leak[None, None, :] * runtime,
        leak_nodram_j=leak[None, None, :] * runtime_nodram,
        dram_j=(dram_tx * LINE_BYTES)[None] * dram_epb,
    )


# The fold's ten outputs in the order the packed kernel lays them out, each
# with its axes over (platform, scenario, design).
_FOLD_LAYOUT = (("l2_read_tx", "s"), ("l2_write_tx", "s"), ("dram_tx", "sd"),
                ("runtime_s", "psd"), ("runtime_nodram_s", "psd"),
                ("dyn_read_j", "sd"), ("dyn_write_j", "sd"),
                ("leak_j", "psd"), ("leak_nodram_j", "psd"),
                ("dram_j", "psd"))


def _pack_outputs(out: dict) -> jax.Array:
    """The fold's output dict raveled into one 1-D buffer, in
    ``_FOLD_LAYOUT`` order."""
    return jnp.concatenate([out[k].ravel() for k, _ in _FOLD_LAYOUT])


@jax.jit
@functools.wraps(_fold)
def _fold_packed(*args):
    """``_fold`` returning one packed buffer, so the host copies a chunk's
    answer out in one transfer instead of ten.  ``_fold`` is looked up
    when traced, not captured here.  The profiler must keep naming the
    program ``_fold`` (hence ``wraps``), so device profiles of the fold
    stay comparable from one version to the next."""
    return _pack_outputs(_fold(*args))


def _unpack(buf: np.ndarray, p: int, s: int, d: int,
            ) -> dict[str, np.ndarray]:
    """The fold's outputs by name as views of one packed host buffer; the
    layout follows from the (platform, scenario, design) sizes alone."""
    size = {"p": p, "s": s, "d": d}
    out, at = {}, 0
    for name, axes in _FOLD_LAYOUT:
        shape = tuple(size[a] for a in axes)
        n = int(np.prod(shape))
        out[name] = buf[at:at + n].reshape(shape)
        at += n
    if at != buf.size:
        raise ValueError(f"packed fold buffer of {buf.size} values is not "
                         f"the ({p}, {s}, {d}) layout's {at}")
    return out


# ---------------------------------------------------------------------------
# Result table
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True, eq=False)
class WorkloadTable:
    """Evaluated [scenario] x [design] workload fold.

    Scenario axis: (workload, batch, training) keys in pack order.  Design
    axis: the CacheDesign points (typically EDAP-tuned reads of an
    ``engine.DesignTable``).  ``runtime_s``/``leak_j`` include the DRAM
    stall term (the scalar path's ``include_dram=True`` default); the
    ``*_nodram`` variants mirror ``include_dram=False``.
    """

    scenarios: tuple[tuple[str, int, bool], ...]
    designs: tuple[CacheDesign, ...]
    platform: Platform
    l2_read_tx: np.ndarray      # [s]
    l2_write_tx: np.ndarray     # [s]
    dram_tx: np.ndarray         # [s, d]
    runtime_s: np.ndarray       # [s, d]
    runtime_nodram_s: np.ndarray
    dyn_read_j: np.ndarray
    dyn_write_j: np.ndarray
    leak_j: np.ndarray
    leak_nodram_j: np.ndarray
    dram_j: np.ndarray

    # -- indexing ----------------------------------------------------------

    def scenario_index(self, workload: str, batch: int, training: bool) -> int:
        return self.scenarios.index((workload, batch, training))

    def design_index(self, mem: str, capacity_bytes: int | None = None) -> int:
        matches = [j for j, d in enumerate(self.designs)
                   if d.mem == mem
                   and capacity_bytes in (None, d.capacity_bytes)]
        if not matches:
            raise ValueError(f"no design ({mem}, {capacity_bytes}) in table")
        if len(matches) > 1:
            if capacity_bytes is None:
                raise ValueError(f"{mem!r} appears at several capacities; "
                                 "pass capacity_bytes")
            # duplicate (mem, capacity) designs — e.g. the same corner at
            # two technology nodes — cannot be told apart here; never
            # silently return the first (SweepResult.design_index parity)
            raise ValueError(f"several designs match ({mem}, "
                             f"{capacity_bytes}); look them up by index")
        return matches[0]

    @property
    def read_write_ratio(self) -> np.ndarray:
        return self.l2_read_tx / np.maximum(1.0, self.l2_write_tx)

    # -- derived metric tensors (scalar EnergyReport operation order) ------

    @property
    def dyn_j(self) -> np.ndarray:
        return self.dyn_read_j + self.dyn_write_j

    def total_j(self, include_dram: bool = False) -> np.ndarray:
        total = self.dyn_j + self.leak_j
        return total + self.dram_j if include_dram else total

    def edp(self, include_dram: bool = False) -> np.ndarray:
        return self.total_j(include_dram) * self.runtime_s

    def metric(self, name: str, include_dram: bool = False) -> np.ndarray:
        """[s, d] tensor of one IsoCapRow.norm metric."""
        return {
            "dyn": lambda: self.dyn_j,
            "leak": lambda: self.leak_j,
            "energy": lambda: self.total_j(include_dram),
            "edp": lambda: self.edp(include_dram),
            "runtime": lambda: self.runtime_s,
        }[name]()

    def norm(self, name: str, mem: str, baseline: str = "sram",
             include_dram: bool = False) -> np.ndarray:
        """[s] metric of `mem`'s design normalized to the baseline design
        (the paper's figure convention; designs looked up by memory)."""
        m = self.metric(name, include_dram)
        return m[:, self.design_index(mem)] / m[:, self.design_index(baseline)]

    # -- scalar-API materialization ----------------------------------------

    def report(self, scenario_index: int, design_index: int) -> EnergyReport:
        """One (scenario, design) cell as the scalar-API EnergyReport."""
        s, d = scenario_index, design_index
        return EnergyReport(
            workload=self.scenarios[s][0],
            mem=self.designs[d].mem,
            runtime_s=float(self.runtime_s[s, d]),
            dyn_read_j=float(self.dyn_read_j[s, d]),
            dyn_write_j=float(self.dyn_write_j[s, d]),
            leak_j=float(self.leak_j[s, d]),
            dram_j=float(self.dram_j[s, d]),
        )

    def reports(self, scenario_index: int) -> dict[str, EnergyReport]:
        """All designs of one scenario, keyed by memory technology (the
        IsoCapRow shape — requires memory-unique designs)."""
        out = {d.mem: self.report(scenario_index, j)
               for j, d in enumerate(self.designs)}
        if len(out) != len(self.designs):
            raise ValueError("designs are not memory-unique; key by index")
        return out


# ---------------------------------------------------------------------------
# Evaluation entry points (memoized, like engine.design_table)
# ---------------------------------------------------------------------------


# Result-tensor names that carry a leading platform axis in the kernel
# output; the rest are platform-independent and shared across the views.
_PLATFORM_DEPENDENT = tuple(k for k, axes in _FOLD_LAYOUT if "p" in axes)


def _fetch(out: jax.Array, devices: int = 1) -> np.ndarray:
    """The packed fold output copied to the host, one blocking copy per
    device buffer (``devices`` of them).  While spans record, the wait for
    the device is its own span, so ``fold.fetch`` holds the copies
    alone."""
    if tracing.enabled():
        with tracing.span("fold.wait"):
            jax.block_until_ready(out)
    with tracing.span("fold.fetch"):
        tracing.count("fold.d2h", devices)
        return np.asarray(out)


def _fetch_tables(out: jax.Array, keys, designs, platforms,
                  ) -> tuple[WorkloadTable, ...]:
    """The plain fold's packed output, copied out once and viewed as one
    WorkloadTable per platform."""
    host = _unpack(_fetch(out), len(platforms), len(keys), len(designs))
    return _tables_from(host, keys, designs, platforms)


def _tables_from(out: dict, keys, designs, platforms,
                 ) -> tuple[WorkloadTable, ...]:
    """One WorkloadTable view per platform from the fold's outputs by name,
    already on the host."""
    shared = {k: v for k, v in out.items() if k not in _PLATFORM_DEPENDENT}
    return tuple(
        WorkloadTable(scenarios=keys, designs=designs, platform=p,
                      **shared,
                      **{k: out[k][i] for k in _PLATFORM_DEPENDENT})
        for i, p in enumerate(platforms))


def _fold_args(batch: StreamBatch, designs: Sequence[CacheDesign],
               platforms: Sequence[Platform]) -> tuple[np.ndarray, ...]:
    """The fold kernel's 13 host inputs for one packed chunk."""
    return (batch.bytes_total, batch.is_write, batch.reuse_distance,
            batch.dram_visible, batch.mask, batch.macs,
            *_design_vectors(designs),
            np.stack([_platform_vector(p) for p in platforms]))


def _dispatch_fold(args: tuple[np.ndarray, ...]) -> jax.Array:
    """One call of the packed fold; each host input lands on one device."""
    with tracing.span("fold.dispatch"):
        tracing.count("fold.h2d", len(args))
        with jax.enable_x64(True):
            return _fold_packed(*args)


@functools.lru_cache(maxsize=None)
def _evaluate_cached(stats_seq: tuple[TrafficStats, ...],
                     designs: tuple[CacheDesign, ...],
                     platforms: tuple[Platform, ...],
                     ) -> tuple[WorkloadTable, ...]:
    with tracing.span("pack"):
        batch = pack(stats_seq)
        args = _fold_args(batch, designs, platforms)
    return _fetch_tables(_dispatch_fold(args), batch.keys, designs,
                         platforms)


def evaluate(stats_seq: Sequence[TrafficStats],
             designs: Sequence[CacheDesign],
             platform: Platform = GTX_1080TI) -> WorkloadTable:
    """Evaluate the [scenario] x [design] cross product as one batched
    computation.  Memoized per (scenarios, designs, platforms), so every
    consumer of the same fold shares one kernel invocation."""
    return evaluate_platforms(stats_seq, designs, (platform,))[0]


def evaluate_platforms(stats_seq: Sequence[TrafficStats],
                       designs: Sequence[CacheDesign],
                       platforms: Sequence[Platform] = (GTX_1080TI,),
                       ) -> tuple[WorkloadTable, ...]:
    """Evaluate the full [platform] x [scenario] x [design] cross product
    as one batched kernel call and return one WorkloadTable view per
    platform (platform-independent tensors are shared between views)."""
    return _evaluate_cached(tuple(stats_seq), tuple(designs),
                            tuple(platforms))


# ---------------------------------------------------------------------------
# Chunk-aware evaluation (sharded mega-sweeps, core/sweep.py ShardPlan)
# ---------------------------------------------------------------------------


def evaluate_chunk(stats_seq: Sequence[TrafficStats],
                   designs: Sequence[CacheDesign],
                   platforms: Sequence[Platform] = (GTX_1080TI,),
                   width: int | None = None,
                   ) -> tuple[WorkloadTable, ...]:
    """One chunk of a sharded sweep: like ``evaluate_platforms`` but
    deliberately **uncached** — a mega-sweep evaluates thousands of chunks
    and pinning every chunk's tensors in the lru memo would unbound peak
    memory — and packed to the chunk's own (bucketed) stream width, so an
    outlier-wide scenario inflates only the chunk that contains it."""
    stats_seq = tuple(stats_seq)
    designs = tuple(designs)
    with tracing.span("pack"):
        if width is None:
            width = pad_width(max(len(s.streams) for s in stats_seq))
        batch = pack(stats_seq, width=width)
        args = _fold_args(batch, designs, platforms)
    return _fetch_tables(_dispatch_fold(args), batch.keys, designs,
                         tuple(platforms))


@functools.lru_cache(maxsize=None)
def _sharded_fold(mesh):
    """The fold, shard_mapped over a 1-D sweep mesh: every input carries a
    leading chunk axis split across devices (the platform matrix is
    replicated), and each device evaluates its chunk independently — the
    fold has no cross-chunk terms, so no collectives are needed.  Each
    chunk's outputs come back packed, one row of a [chunk, N] buffer, so
    a group is one device buffer per device."""
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import SWEEP_AXIS

    sh = P(SWEEP_AXIS)

    def body(bt, iw, rd, vis, mask, macs, rl, wl, re_, we_, leak, caps,
             pmat):
        out = _fold(bt[0], iw[0], rd[0], vis[0], mask[0], macs[0],
                    rl[0], wl[0], re_[0], we_[0], leak[0], caps[0], pmat)
        return _pack_outputs(out)[None]

    return jax.jit(jax.shard_map(body, mesh=mesh,
                                 in_specs=(sh,) * 12 + (P(),),
                                 out_specs=sh))


def evaluate_chunk_group(chunk_stats: Sequence[Sequence[TrafficStats]],
                         chunk_designs: Sequence[Sequence[CacheDesign]],
                         platforms: Sequence[Platform],
                         mesh) -> list[tuple[WorkloadTable, ...]]:
    """Evaluate one mesh-width group of same-shaped chunks data-parallel
    across devices via ``shard_map`` (uncached, like ``evaluate_chunk``).

    All chunks must agree on scenario and design counts (the sharded
    lowering groups them so); the group packs to one shared (bucketed)
    stream width.  Returns the per-chunk WorkloadTable views, in order.
    """
    g = len(chunk_stats)
    if g != mesh.devices.size:
        raise ValueError(f"group of {g} chunks on a {mesh.devices.size}"
                         "-device mesh; groups must fill the mesh")
    if len({len(cs) for cs in chunk_stats}) != 1 or \
            len({len(cd) for cd in chunk_designs}) != 1:
        raise ValueError("chunks in a sharded group must share scenario "
                         "and design counts")
    with tracing.span("pack", chunks=g):
        width = pad_width(max(len(s.streams)
                              for cs in chunk_stats for s in cs))
        batches = [pack(tuple(cs), width=width) for cs in chunk_stats]
        stacked = [np.stack([getattr(b, f) for b in batches])
                   for f in ("bytes_total", "is_write", "reuse_distance",
                             "dram_visible", "mask", "macs")]
        vecs = [np.stack(v) for v in
                zip(*(_design_vectors(tuple(cd)) for cd in chunk_designs))]
        pmat = np.stack([_platform_vector(p) for p in platforms])
    with tracing.span("fold.dispatch", chunks=g):
        # each chunk-axis input splits one slice onto every device of the
        # mesh; the platform matrix is replicated onto each
        tracing.count("fold.h2d", (len(stacked) + len(vecs) + 1) * g)
        with jax.enable_x64(True):
            out = _sharded_fold(mesh)(*stacked, *vecs, pmat)
    out = _fetch(out, devices=g)
    p, s, d = len(platforms), len(chunk_stats[0]), len(chunk_designs[0])
    with tracing.span("assemble", chunks=g):
        return [_tables_from(_unpack(out[i], p, s, d), batches[i].keys,
                             tuple(chunk_designs[i]), tuple(platforms))
                for i in range(g)]


# ---------------------------------------------------------------------------
# Bucketed evaluation + warmup (the concurrent sweep service's fold path)
# ---------------------------------------------------------------------------


def _pad_axis(a: np.ndarray, n: int, fill) -> np.ndarray:
    """Pad the leading axis of ``a`` to length ``n`` with ``fill``."""
    if a.shape[0] == n:
        return a
    out = np.full((n,) + a.shape[1:], fill, dtype=a.dtype)
    out[:a.shape[0]] = a
    return out


def evaluate_bucketed(stats_seq: Sequence[TrafficStats],
                      designs: Sequence[CacheDesign],
                      platforms: Sequence[Platform] = (GTX_1080TI,),
                      ) -> tuple[WorkloadTable, ...]:
    """Shape-bucketed, uncached fold — the sweep service's evaluation path.

    Pads the scenario axis, the design axis, and the stream width each to
    its power-of-two bucket and slices the real cells back out of the
    kernel output.  Padding is inert by construction: scenario rows carry
    zero bytes, infinite reuse distance, zero MACs, and a False mask;
    design columns are all-zero vectors (zero capacity means every stream
    misses, but the column is dropped before anything reads it).  The set
    of compiled kernel shapes is therefore O(log^3) over arbitrary
    request sizes — exactly the shapes :func:`warmup` pre-traces, which
    is what makes a warmed service answer never-seen specs at warm cost.

    Values match ``evaluate_platforms`` at <= 1e-12 relative (padding
    reassociates the stream reductions, so bit-identity is not claimed).
    Deliberately uncached like ``evaluate_chunk``: the service layers its
    own bounded result cache on top.
    """
    stats_seq = tuple(stats_seq)
    designs = tuple(designs)
    platforms = tuple(platforms)
    s, d = len(stats_seq), len(designs)
    sp = axis_bucket(s, S_BUCKET_FLOOR)
    dp = axis_bucket(d, D_BUCKET_FLOOR)
    width = pad_width(max(len(x.streams) for x in stats_seq))
    batch = pack(stats_seq, width=width)
    bt = _pad_axis(batch.bytes_total, sp, 0.0)
    iw = _pad_axis(batch.is_write, sp, False)
    rd = _pad_axis(batch.reuse_distance, sp, np.inf)
    vis = _pad_axis(batch.dram_visible, sp, False)
    mask = _pad_axis(batch.mask, sp, False)
    macs = _pad_axis(batch.macs, sp, 0.0)
    vecs = [np.pad(v, (0, dp - d)) for v in _design_vectors(designs)]
    pmat = np.stack([_platform_vector(p) for p in platforms])
    with jax.enable_x64(True):
        out = _fold_packed(bt, iw, rd, vis, mask, macs, *vecs, pmat)
    padded = _unpack(np.asarray(out), len(platforms), sp, dp)
    real = {"p": slice(None), "s": slice(s), "d": slice(d)}
    sliced = {k: padded[k][tuple(real[a] for a in axes)]
              for k, axes in _FOLD_LAYOUT}
    return _tables_from(sliced, batch.keys, designs, platforms)


def fold_shape(n_scenarios: int, max_streams: int, n_designs: int,
               n_platforms: int) -> tuple[int, int, int, int]:
    """The (s, k, d, p) kernel shape ``evaluate_bucketed`` compiles for
    these axis sizes — the unit of warmup."""
    return (axis_bucket(n_scenarios, S_BUCKET_FLOOR), pad_width(max_streams),
            axis_bucket(n_designs, D_BUCKET_FLOOR), int(n_platforms))


def warmup_fold(shape: tuple[int, int, int, int]) -> None:
    """Compile (and prime the jit dispatch cache for) the fold kernel at
    one bucketed (s, k, d, p) shape by folding inert dummy data — the
    same argument shapes/dtypes ``evaluate_bucketed`` dispatches, so a
    later real request at this shape pays only numeric work (~ms), not
    the XLA compile (~0.5 s)."""
    s, k, d, p = shape
    zeros_sk = np.zeros((s, k))
    false_sk = np.zeros((s, k), dtype=bool)
    vec = np.zeros(d)
    pmat = np.ones((p, len(PLATFORM_FIELDS)))  # ones: no 0-divides
    with jax.enable_x64(True):
        _fold_packed(zeros_sk, false_sk, np.full((s, k), np.inf), false_sk,
                     false_sk, np.zeros(s), vec, vec, vec, vec, vec,
                     np.ones(d), pmat)


def warmup(scenario_buckets: Sequence[int] = (S_BUCKET_FLOOR, 16),
           width_buckets: Sequence[int] = (16, 1024),
           design_buckets: Sequence[int] = (D_BUCKET_FLOOR, 16),
           platform_counts: Sequence[int] = (1, 2)) -> int:
    """Pre-trace the fold kernel over a grid of common bucketed shapes
    (spec-independent warmup; the service's spec-driven warmup compiles
    exact request shapes instead).  Returns the number of distinct shapes
    compiled.  The defaults cover small CNN/LM specs (width 16) and the
    wide-scenario regime (googlenet train packs at width 1024)."""
    shapes = {fold_shape(s, k, d, p)
              for s in scenario_buckets for k in width_buckets
              for d in design_buckets for p in platform_counts}
    for shape in sorted(shapes):
        warmup_fold(shape)
    return len(shapes)


def dram_tx(stats_seq: Sequence[TrafficStats],
            capacities_bytes: Sequence[float]) -> np.ndarray:
    """[s, c] DRAM transactions at each capacity — the batched form of
    ``TrafficStats.dram_tx`` (paper Fig. 6's capacity sweep)."""
    batch = pack(stats_seq)
    caps = np.array([float(c) for c in capacities_bytes], dtype=np.float64)
    with jax.enable_x64(True):
        out = _miss_tx_kernel(batch.bytes_total, batch.reuse_distance,
                              batch.dram_visible & batch.mask, caps)
    return np.asarray(out)


# cache_clear()/cache_info()-style hooks on the public entry points, so
# consumers (and the cache-key-drift test in tests/test_sweep.py) can
# observe and reset the memoization without reaching for the private
# lru-cached implementation.
evaluate.cache_clear = _evaluate_cached.cache_clear
evaluate.cache_info = _evaluate_cached.cache_info
evaluate_platforms.cache_clear = _evaluate_cached.cache_clear
evaluate_platforms.cache_info = _evaluate_cached.cache_info


def clear_caches() -> None:
    """Drop memoized stats and tables (benchmark reruns)."""
    stats_for.cache_clear()
    _evaluate_cached.cache_clear()
