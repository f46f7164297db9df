"""Bitcell characterization — reproduces paper Table I.

The paper's circuit-level flow (§III-A): parametrized SPICE netlists where
read/write pulse widths are modulated to the point of failure, sweeping the
access-device fin count to find the optimal latency/energy/area balance.

Our equivalent: analytic MTJ switching models (core/mtj.py) + a fin-count
sweep under real layout feasibility constraints:

  * A 2-poly-pitch MRAM bitcell accommodates at most MAX_FINS=4 fins total
    (the bitcell-area formulation of Seo & Roy [45] that the paper uses).
  * STT shares one access transistor between read and write paths, so all
    fins serve both; the write current must exceed the MTJ critical current
    (feasibility), and reads are capped by the short-pulse read-disturb
    ceiling (wordline under-drive).
  * SOT has decoupled read/write devices; both need >= 1 fin within the
    same 4-fin budget, and the write path must exceed Ic0 of the SOT line.

The sweep minimizes a bitcell-level EDAP metric over feasible assignments.
Outcomes (validated in tests/benchmarks against Table I): STT -> 4 shared
fins; SOT -> 3 write + 1 read fins — feasibility alone forces both, which
matches the paper's chosen design points.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

from . import mtj, tech
from .tech import TechNode, TECH_16NM

MAX_FINS = 4  # 2-poly-pitch bitcell fin budget ([45] layout formulation)

# Bitcell parameters consumed by the cache PPA equations, in the order the
# batched engine (core/engine.py) packs them into per-technology vectors.
ARRAY_FIELDS = (
    "read_current_a",
    "sense_latency_s",
    "sense_energy_j",
    "write_latency_avg_s",
    "write_energy_avg_j",
    "area_norm",
    "cell_leakage_w",
)

# Bitcell footprint vs fin count, normalized to the foundry 6T SRAM cell,
# at the 16 nm anchor.  Linear-in-fins with a per-structure base term
# ([45]); SOT's shared-bitline structure has the smaller base despite its
# second device.  Across nodes the base term (MTJ pillar + BEOL keep-out,
# via/metal-pitch limited) shrinks slower than the 6T footprint while the
# fin term (front-end devices) tracks it — tech.BITCELL_SCALING_EXPONENTS.
_AREA_BASE = {"stt": 0.10, "sot": 0.05}
_AREA_PER_FIN = 0.06

# Read-path current per fin at the 16 nm anchor.  Writes drive the full
# I_on; reads are derated: STT under-drives the read wordline to respect
# the read-disturb ceiling, SOT's read current is series-limited by the MTJ
# stack resistance.  Both MRAM access paths derate with the supply at
# scaled nodes (i_read/i_write_per_fin exponents).
_I_READ_PER_FIN = {"stt": 42e-6, "sot": 38.5e-6}
# Short-pulse (650 ps << thermal switching time) read-disturb ceiling for
# shared-path STT reads: 1.05x the smaller critical current.
_STT_READ_CAP_FRAC = 1.05

# Intrinsic 6T read/write time and ~fJ/bit bitline swing energy at 16 nm
# (sram_bitcell anchors; CV/I and CV^2 node scaling).
_SRAM_T_RW = 120e-12
_SRAM_E_RW = 1.3e-15


def _bitcell_scale(name: str, node: TechNode) -> float:
    """s**exp factor of one bitcell-level quantity at ``node`` (exactly 1.0
    at the 16 nm anchor)."""
    return tech.scale_factor(node) ** tech.BITCELL_SCALING_EXPONENTS[name]


@dataclasses.dataclass(frozen=True)
class Bitcell:
    """Characterized bitcell — the rows of paper Table I."""

    name: str
    sense_latency_s: float
    sense_energy_j: float
    write_latency_set_s: float
    write_latency_reset_s: float
    write_energy_set_j: float
    write_energy_reset_j: float
    fins_read: int
    fins_write: int
    area_norm: float            # normalized to foundry SRAM bitcell
    cell_leakage_w: float       # storage-cell leakage (0 for MRAM cores)
    read_current_a: float

    @property
    def write_latency_avg_s(self) -> float:
        return 0.5 * (self.write_latency_set_s + self.write_latency_reset_s)

    @property
    def write_energy_avg_j(self) -> float:
        return 0.5 * (self.write_energy_set_j + self.write_energy_reset_j)

    @property
    def shares_access_device(self) -> bool:
        return self.name == "stt"

    def as_array(self) -> np.ndarray:
        """Parameter vector (float64, ARRAY_FIELDS order) for the batched
        engine: one row of the per-technology parameter matrix."""
        return np.array([getattr(self, f) for f in ARRAY_FIELDS],
                        dtype=np.float64)


def _read_current(tech_name: str, dev: mtj.MTJDevice, node: TechNode,
                  fins: int) -> float:
    i = fins * _I_READ_PER_FIN[tech_name] * _bitcell_scale("i_read_per_fin",
                                                           node)
    if tech_name == "stt":
        # Reads use the set-polarity current direction, so the short-pulse
        # disturb ceiling is referenced to Ic0(set).
        i = min(i, _STT_READ_CAP_FRAC * dev.ic0_set_a)
    return i


def _write_current(node: TechNode, fins_write: int) -> float:
    """MRAM write-path drive: full per-fin I_on derated by the node's
    write-path headroom factor (tech.BITCELL_SCALING_EXPONENTS)."""
    return fins_write * node.ion_per_fin_a \
        * _bitcell_scale("i_write_per_fin", node)


def base_area_norm(tech_name: str, node: TechNode = TECH_16NM) -> float:
    """The fin-independent bitcell footprint term (MTJ pillar + BEOL
    keep-out, normalized to the foundry 6T cell) at ``node`` — the anchor
    value every ``area_base_norm`` override (inverse-design leaf) is
    centered on."""
    return _AREA_BASE[tech_name] * _bitcell_scale("area_base", node)


def fin_assignments(tech_name: str) -> tuple[tuple[int, int, bool], ...]:
    """The full layout-feasible ``(fins_read, fins_write, shared)`` grid the
    characterization sweep enumerates: STT shares one access device across
    both paths (1..MAX_FINS shared fins); SOT decouples them, each path
    needs >= 1 fin, and the pair fits the same MAX_FINS budget.  Static —
    the inverse path's softmin relaxes over exactly this tuple."""
    if tech_name == "stt":
        return tuple((f, f, True) for f in range(1, MAX_FINS + 1))
    if tech_name == "sot":
        return tuple((fr, fw, False)
                     for fr in range(1, MAX_FINS)
                     for fw in range(1, MAX_FINS)
                     if fr + fw <= MAX_FINS)
    raise ValueError(f"no fin sweep for tech {tech_name!r}")


def assemble(tech_name: str, node: TechNode, fins_read: int, fins_write: int,
             shared: bool, *, device: mtj.MTJDevice | None = None,
             area_base_norm: float | None = None) -> Bitcell | None:
    """Assemble one explicit fin assignment into a :class:`Bitcell`
    (None if infeasible) — the standard-path re-evaluation entry for
    inverse design: ``device`` substitutes a :func:`mtj.custom_device`
    with converged leaves and ``area_base_norm`` overrides the
    fin-independent footprint term (default :func:`base_area_norm`)."""
    dev = mtj.device(tech_name, node) if device is None else device
    return _evaluate(tech_name, dev, node, fins_read, fins_write, shared,
                     area_base_norm=area_base_norm)


def _evaluate(tech_name: str, dev: mtj.MTJDevice, node: TechNode,
              fins_read: int, fins_write: int, shared: bool,
              area_base_norm: float | None = None) -> Bitcell | None:
    """Evaluate one fin assignment; None if infeasible."""
    total_fins = fins_write if shared else fins_read + fins_write
    if total_fins > MAX_FINS or fins_read < 1 or fins_write < 1:
        return None
    i_write = _write_current(node, fins_write)
    t_set = mtj.switching_time(dev, i_write, reset=False)
    t_reset = mtj.switching_time(dev, i_write, reset=True)
    if not (math.isfinite(t_set) and math.isfinite(t_reset)):
        return None  # below critical current: write never completes
    i_read = _read_current(tech_name, dev, node, fins_read)
    if area_base_norm is None:
        area_base_norm = base_area_norm(tech_name, node)
    return Bitcell(
        name=tech_name,
        sense_latency_s=dev.sense_time_s,
        sense_energy_j=mtj.sense_energy(dev, i_read, node.vdd_v),
        write_latency_set_s=t_set,
        write_latency_reset_s=t_reset,
        write_energy_set_j=mtj.switching_energy(dev, i_write, reset=False),
        write_energy_reset_j=mtj.switching_energy(dev, i_write, reset=True),
        fins_read=fins_read,
        fins_write=fins_write,
        area_norm=area_base_norm
        + _AREA_PER_FIN * _bitcell_scale("area_per_fin", node) * total_fins,
        cell_leakage_w=total_fins * node.ioff_per_fin_a * node.vdd_v,
        read_current_a=i_read,
    )


def _edap(cell: Bitcell) -> float:
    """Bitcell-level energy-delay-area objective for the fin sweep."""
    ed = (cell.sense_latency_s * cell.sense_energy_j
          + cell.write_latency_avg_s * cell.write_energy_avg_j)
    return ed * cell.area_norm


def characterize(tech_name: str, node: TechNode = TECH_16NM) -> Bitcell:
    """Fin-count sweep (paper §III-A) -> EDAP-optimal bitcell.

    The sweep runs on the node-projected device (``mtj.device``) with
    node-derated drive currents, so a scaled node re-characterizes the
    bitcell on genuinely scaled physics.  If no fin assignment's write
    current clears the device's critical current — the STT scaling wall at
    deep nodes, where drive derates faster than the retention-pinned Ic0 —
    the raised diagnostic says exactly how far short the best drive falls.
    """
    if tech_name == "sram":
        return sram_bitcell(node)
    dev = mtj.device(tech_name, node)
    assignments = fin_assignments(tech_name)
    candidates = [cell for fr, fw, shared in assignments
                  if (cell := _evaluate(tech_name, dev, node, fr, fw,
                                        shared)) is not None]
    max_write_fins = max(fw for _, fw, _ in assignments)
    if not candidates:
        best_i = _write_current(node, max_write_fins)
        ic0 = max(dev.ic0_set_a, dev.ic0_reset_a)
        raise ValueError(
            f"no feasible {tech_name} bitcell at node {node.name!r}: the "
            f"best available write current ({max_write_fins} fins -> "
            f"{best_i * 1e6:.1f} uA) does not exceed the device critical "
            f"current (Ic0 = {ic0 * 1e6:.1f} uA) — the node's drive derates "
            "below the switching threshold (see "
            "tech.BITCELL_SCALING_EXPONENTS / tech.MTJ_SCALING_EXPONENTS)")
    return min(candidates, key=_edap)


def sram_bitcell(node: TechNode = TECH_16NM) -> Bitcell:
    """Foundry 6T SRAM bitcell (the Table I normalization baseline).

    SRAM has no MTJ: reads/writes are bitline (dis)charge events, fast and
    symmetric; the storage cell itself leaks continuously (the scalability
    problem the paper targets).  Cell leakage comes from the node:
    ``TechNode.sram_cell_leak_w`` is calibrated at the 16 nm anchor so the
    3 MB EDAP-tuned cache reproduces Table II's 6442 mW, and scaled nodes
    carry their own (worsening) projection — the cross-node SRAM leakage
    trend the DTCO analysis reads.  The intrinsic 6T access time and energy
    scale with the node too (CV/I and CV^2 rules,
    tech.BITCELL_SCALING_EXPONENTS).
    """
    t_rw = _SRAM_T_RW * _bitcell_scale("sram_t_rw", node)
    e_rw = _SRAM_E_RW * _bitcell_scale("sram_e_rw", node)
    return Bitcell(
        name="sram",
        sense_latency_s=t_rw,
        sense_energy_j=e_rw,
        write_latency_set_s=t_rw,
        write_latency_reset_s=t_rw,
        write_energy_set_j=e_rw,
        write_energy_reset_j=e_rw,
        fins_read=2,
        fins_write=2,
        area_norm=1.0,
        cell_leakage_w=node.sram_cell_leak_w,
        read_current_a=2 * node.ion_per_fin_a,
    )


def table1() -> dict[str, Bitcell]:
    """All three characterized bitcells (paper Table I + SRAM baseline)."""
    return {name: characterize(name) for name in ("sram", "stt", "sot")}
