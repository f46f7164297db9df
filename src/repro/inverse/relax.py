"""The differentiable lowering: device leaves -> soft bitcells -> PPA ->
workload fold -> softmin-selected objective.

This is the unmemoized, non-argmin variant of the standard pipeline.
Three discrete choices become temperature-annealed softmin relaxations:

* the **fin assignment** of each NVM bitcell (the ``bitcell.
  fin_assignments`` grid): every assignment's 7-vector is evaluated with
  the *same scalar operation order* as ``bitcell._evaluate`` (at a hard
  temperature the mixture weights are exactly one-hot, so the cell
  matches the winning assignment's vector to the few ulps the
  ``exp(ln(anchor))`` theta round-trip introduces), infeasible
  assignments (write current below Ic0) are masked with -inf logits,
  and the mixture weights are a softmin over the bitcell EDAP;
* the **(mem, capacity, node) corner x organization** selection: one
  ``engine.ppa_fn`` call over the unique node/mem/capacity cross
  product (the same compiled kernel the memoized path dispatches — a
  traced cell matrix composes with ``jax.grad`` through the jit), the
  per-corner tensors are gathered by static index arrays, the workload
  objective folds through ``workload_engine._fold``, and a joint
  softmin over all valid (corner, org) cells yields the relaxed
  objective and area;
* the **STT scaling wall**: instead of ``characterize``'s raised
  diagnostic, the best overdrive across assignments enters the loss as
  a softplus penalty, so the optimizer feels the wall as a smooth
  gradient (and the extrapolated 2 nm node is a finite, differentiable
  point instead of an exception).

Everything discrete about the problem (the spec axes, the assignment
grids, the validity masks, platform/stream tensors) is precomputed as
numpy constants at lowering time; the traced functions are pure maps
from ``theta = ln(leaves)`` (and a temperature) to scalars, so the
driver can ``jit``/``vmap``/``grad`` them freely.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitcell as bitcell_mod
from repro.core import calibration, engine, workload_engine
from repro.core.bitcell import (
    _AREA_PER_FIN,
    _I_READ_PER_FIN,
    _STT_READ_CAP_FRAC,
    _bitcell_scale,
)
from repro.core.sweep import DesignPoint
from repro.core.tech import TechNode
from repro.inverse import bounds
from repro.inverse.bounds import LeafGroup, N_LEAVES
from repro.inverse.problem import InverseProblem

# Temperature at which the softmins are exactly one-hot in float64 (the
# smallest log-metric gaps in this model are ~1e-2; 1e-2 / 1e-4 = 100
# nats underflows the runner-up weight to exactly 0.0).
HARD_TEMP = 1e-4

# Overdrive scale of the scaling-wall softplus penalty: the wall "turns
# on" within ~0.05 of zero overdrive.
WALL_SCALE = 0.05
LAMBDA_WALL = 10.0
# Area-budget hinge: softplus((soft_area/budget - 1) / SIGMA) — stiff
# within ~1% of the budget.
SIGMA_AREA = 0.01
LAMBDA_AREA = 50.0


@dataclasses.dataclass(frozen=True)
class _Assignment:
    """Static per-fin-assignment constants (scalar op order preserved)."""

    fins_read: int
    fins_write: int
    shared: bool
    i_write_a: float       # bitcell._write_current(node, fins_write)
    i_read_raw_a: float    # read current before the STT disturb cap
    fin_area_norm: float   # the fins' footprint term
    cell_leak_w: float


def _assignments(flavor: str, node: TechNode) -> tuple[_Assignment, ...]:
    out = []
    for fr, fw, shared in bitcell_mod.fin_assignments(flavor):
        total_fins = fw if shared else fr + fw
        out.append(_Assignment(
            fins_read=fr, fins_write=fw, shared=shared,
            i_write_a=bitcell_mod._write_current(node, fw),
            i_read_raw_a=fr * _I_READ_PER_FIN[flavor]
            * _bitcell_scale("i_read_per_fin", node),
            fin_area_norm=_AREA_PER_FIN
            * _bitcell_scale("area_per_fin", node) * total_fins,
            cell_leak_w=total_fins * node.ioff_per_fin_a * node.vdd_v,
        ))
    return tuple(out)


def soft_cell(theta_g, group: LeafGroup, temp):
    """Softmin fin-assignment mixture of one NVM (flavor, node) group.

    ``theta_g`` is the group's ln-leaf slice.  Returns (cell [7] in
    bitcell.ARRAY_FIELDS order, best overdrive across assignments —
    the scaling-wall signal, > 0 iff some assignment is feasible).

    Every per-assignment expression mirrors ``bitcell._evaluate`` /
    ``mtj.switching_time`` / ``mtj.switching_energy`` operation order;
    at :data:`HARD_TEMP` the mixture weights are exactly one-hot, so
    the cell equals the winning assignment's ``Bitcell.as_array()`` up
    to the few ulps of the ``exp(ln(anchor))`` theta round-trip.
    """
    (ic0_set_a, ic0_reset_a, tau_set_s, tau_reset_s, r_set_ohm,
     r_reset_ohm, sense_time_s, area_base) = (
        jnp.exp(theta_g[i]) for i in range(N_LEAVES))
    node = group.node
    vecs, edaps, od_mins = [], [], []
    for a in _assignments(group.flavor, node):
        od_set = a.i_write_a / ic0_set_a - 1.0
        od_reset = a.i_write_a / ic0_reset_a - 1.0
        od_min = jnp.minimum(od_set, od_reset)
        # a non-positive overdrive only feeds a masked (weight-0) branch:
        # divide it by 1.0 so that branch and its 1/od^2 gradient stay
        # within float32's exponent range, which bounds the TPU's
        # emulated float64 (0 * inf would poison the mixture's gradient)
        t_set_s = tau_set_s / jnp.where(od_set > 0.0, od_set, 1.0)
        t_reset_s = tau_reset_s / jnp.where(od_reset > 0.0, od_reset, 1.0)
        if group.flavor == "stt":
            i_read_a = jnp.minimum(a.i_read_raw_a,
                                   _STT_READ_CAP_FRAC * ic0_set_a)
        else:
            i_read_a = jnp.asarray(a.i_read_raw_a, dtype=jnp.float64)
        sense_e_j = node.vdd_v * i_read_a * sense_time_s
        e_set_j = a.i_write_a * a.i_write_a * r_set_ohm * t_set_s
        e_reset_j = a.i_write_a * a.i_write_a * r_reset_ohm * t_reset_s
        wlat_avg_s = 0.5 * (t_set_s + t_reset_s)
        we_avg_j = 0.5 * (e_set_j + e_reset_j)
        area_norm = area_base + a.fin_area_norm
        vecs.append(jnp.stack([
            i_read_a, sense_time_s, sense_e_j, wlat_avg_s, we_avg_j,
            area_norm, jnp.asarray(a.cell_leak_w, dtype=jnp.float64)]))
        edaps.append((sense_time_s * sense_e_j + wlat_avg_s * we_avg_j)
                     * area_norm)
        od_mins.append(od_min)
    edap = jnp.stack(edaps)
    od_best = jnp.stack(od_mins).max()
    logits = jnp.where(jnp.stack(od_mins) > 0.0,
                       -jnp.log(edap) / temp, -jnp.inf)
    w = jax.nn.softmax(logits)
    cell = (w[:, None] * jnp.stack(vecs)).sum(axis=0)
    return cell, od_best


def _iso_budget(areas_mm2: np.ndarray) -> float:
    """The "iso" area budget: the largest grid-corner area — every grid
    corner is admissible, and the optimum is compared at equal area."""
    return float(np.max(areas_mm2))


@dataclasses.dataclass(frozen=True, eq=False)
class Lowered:
    """A problem lowered to pure traced functions of theta.

    Static structure (axes, index maps, stream/platform tensors, leaf
    groups and bounds) is precomputed; :meth:`loss`, :meth:`metrics`,
    and :meth:`scenario_objective` are pure jnp maps suitable for
    ``jit``/``grad``/``vmap``.  Build via :func:`lower`.
    """

    problem: InverseProblem
    points: tuple[DesignPoint, ...]
    groups: tuple[LeafGroup, ...]
    theta0: np.ndarray           # centers, ln space
    theta_lo: np.ndarray
    theta_hi: np.ndarray
    area_budget_mm2: float | None
    # unique-axis structure
    nodes: tuple[TechNode, ...]
    mems: tuple[str, ...]
    caps: tuple[int, ...]
    nk: np.ndarray               # [k] node index per point
    mk: np.ndarray               # [k] mem index
    ck: np.ndarray               # [k] capacity index
    # kernel constants
    cal_mat: np.ndarray          # [n, m, 8]
    is_sram: np.ndarray          # [m]
    node4: np.ndarray            # [n, 4]
    peri: np.ndarray             # [n, 7]
    caps_arr: np.ndarray         # [c] int64
    const_cells: dict            # (ni, mi) -> [7] np row (non-relaxed)
    relaxed: dict                # (ni, mi) -> group index
    valid: np.ndarray            # [k, o] bool
    caps_k: np.ndarray           # [k] float64 capacity per point
    # fold constants ("edp" objective)
    batch: workload_engine.StreamBatch | None
    pmat: np.ndarray | None

    # -- traced pipeline ---------------------------------------------------

    def _cell_mat(self, theta, temp):
        """[n, m, 7] cell matrix: soft NVM rows, constant sram rows; also
        the per-group best overdrives (the scaling-wall signals)."""
        cells = {}
        od_bests = [None] * len(self.groups)
        for (ni, mi), gi in self.relaxed.items():
            g = self.groups[gi]
            sl = theta[g.offset:g.offset + N_LEAVES]
            cell, od_best = soft_cell(sl, g, temp)
            cells[(ni, mi)] = cell
            od_bests[gi] = od_best
        rows = [jnp.stack([
            cells[(ni, mi)] if (ni, mi) in cells
            else jnp.asarray(self.const_cells[(ni, mi)])
            for mi in range(len(self.mems))])
            for ni in range(len(self.nodes))]
        return jnp.stack(rows), od_bests

    def _ppa(self, theta, temp):
        """Gathered per-point PPA: (rl, wl, re, we) [k, o], leak/area [k],
        plus the per-group overdrives."""
        cell_mat, od_bests = self._cell_mat(theta, temp)
        out = engine.ppa_fn(cell_mat, self.cal_mat, self.is_sram,
                            self.node4, self.peri, self.caps_arr,
                            engine.ORG_BANKS, engine.ORG_ROWS,
                            engine.ORG_COLS, engine.ORG_ACCESS,
                            anchor_peri=False)
        nk, mk, ck = self.nk, self.mk, self.ck
        return (out["read_latency_s"][nk, mk, ck],
                out["write_latency_s"][nk, mk, ck],
                out["read_energy_j"][nk, mk, ck],
                out["write_energy_j"][nk, mk, ck],
                out["leakage_w"][nk, mk, ck],
                out["area_mm2"][nk, mk, ck],
                od_bests)

    def _fold_edp(self, rl, wl, re_, we_, leak):
        """[p, s, k, o] EDP through the workload fold (the scalar
        WorkloadTable.edp operation order)."""
        k, o = rl.shape
        b = self.batch
        # eager (numpy-backed) calls warn on the rd=inf streams' inf/inf
        # before the fold's where() masks them; the jitted path is silent
        with np.errstate(invalid="ignore"):
            out = workload_engine._fold(
                b.bytes_total, b.is_write, b.reuse_distance,
                b.dram_visible, b.mask, b.macs,
                rl.reshape(-1), wl.reshape(-1), re_.reshape(-1),
                we_.reshape(-1), jnp.repeat(leak, o),
                np.repeat(self.caps_k, o), self.pmat)
        total = out["dyn_read_j"][None] + out["dyn_write_j"][None] \
            + out["leak_j"]
        if self.problem.include_dram:
            total = total + out["dram_j"]
        edp = total * out["runtime_s"]                     # [p, s, k*o]
        return edp.reshape(edp.shape[0], edp.shape[1], k, o)

    def _objective(self, rl, wl, re_, we_, leak, area):
        """[k, o] objective tensor from gathered PPA quantities.  Shared
        by the relaxed path and :meth:`grid_objective`, so softmin ->
        argmin recovery is consistent by construction."""
        if self.problem.objective == "edap":
            e = 0.5 * (re_ + we_)
            d = 0.5 * (rl + wl)
            return e * d * area[:, None]
        edp = self._fold_edp(rl, wl, re_, we_, leak)
        return edp.mean(axis=(0, 1))

    def objective_matrix(self, theta, temp=HARD_TEMP):
        """([k, o] objective, [k] area, per-group overdrives) at the
        given fin-mixture temperature."""
        rl, wl, re_, we_, leak, area, od_bests = self._ppa(theta, temp)
        return self._objective(rl, wl, re_, we_, leak, area), area, od_bests

    def loss(self, theta, temp):
        """The annealed scalar loss: softmin objective + area hinge +
        scaling-wall penalty (target mode squares the log residual)."""
        obj, area, od_bests = self.objective_matrix(theta, temp)
        obj_safe = jnp.where(self.valid, obj, 1.0)
        logits = jnp.where(self.valid, -jnp.log(obj_safe) / temp,
                           -jnp.inf).reshape(-1)
        w = jax.nn.softmax(logits).reshape(obj.shape)
        soft_obj = (w * obj_safe).sum()
        soft_area = (w.sum(axis=1) * area).sum()
        if self.problem.target is not None:
            out = (jnp.log(soft_obj)
                   - math.log(self.problem.target)) ** 2
        else:
            out = jnp.log(soft_obj)
        if self.area_budget_mm2 is not None:
            out = out + LAMBDA_AREA * jax.nn.softplus(
                (soft_area / self.area_budget_mm2 - 1.0) / SIGMA_AREA)
        for od_best in od_bests:
            out = out + LAMBDA_WALL * jax.nn.softplus(-od_best / WALL_SCALE)
        return out

    def wall_penalty(self, theta):
        """The scaling-wall penalty alone (diagnostic; ~0 when every
        group has overdrive headroom, large past the wall)."""
        _, od_bests = self._cell_mat(theta, HARD_TEMP)
        pen = 0.0
        for od_best in od_bests:
            pen = pen + LAMBDA_WALL * jax.nn.softplus(-od_best / WALL_SCALE)
        return pen

    def scenario_objective(self, theta, org_idx: tuple[int, ...]):
        """ln objective per (platform, scenario) at fixed per-point orgs
        — the sensitivity layer's map ([p, s, k]; "edap" has no scenario
        axis and returns ln EDAP [1, 1, k])."""
        rl, wl, re_, we_, leak, area, _ = self._ppa(theta, HARD_TEMP)
        oi = np.asarray(org_idx)
        kk = np.arange(len(self.points))
        if self.problem.objective == "edap":
            e = 0.5 * (re_[kk, oi] + we_[kk, oi])
            d = 0.5 * (rl[kk, oi] + wl[kk, oi])
            return jnp.log(e * d * area)[None, None, :]
        edp = self._fold_edp(rl[kk, oi][:, None], wl[kk, oi][:, None],
                             re_[kk, oi][:, None], we_[kk, oi][:, None],
                             leak)
        return jnp.log(edp[..., 0])

    # -- hardened / reference evaluations ----------------------------------

    def masked_argmin(self, obj: np.ndarray, area: np.ndarray,
                      ) -> tuple[int, int]:
        """(point, org) argmin over valid cells within the area budget."""
        mask = np.array(self.valid)
        if self.area_budget_mm2 is not None:
            mask = mask & (np.asarray(area)[:, None]
                           <= self.area_budget_mm2 * (1.0 + 1e-9))
        if not mask.any():
            raise ValueError("no (corner, org) cell satisfies the area "
                             f"budget {self.area_budget_mm2} mm^2")
        flat = int(np.argmin(np.where(mask, np.asarray(obj), np.inf)))
        return flat // engine.N_ORGS, flat % engine.N_ORGS

    def grid_objective(self) -> tuple[np.ndarray, np.ndarray]:
        """([k, o] objective, [k] area) through the standard memoized
        engine path (``engine.design_table``) with anchor leaves — the
        grid-argmin reference the relaxation is checked against."""
        table = engine.design_table(self.mems, self.caps, nodes=self.nodes)
        nk, mk, ck = self.nk, self.mk, self.ck
        obj = self._objective(
            table.read_latency_s[nk, mk, ck],
            table.write_latency_s[nk, mk, ck],
            table.read_energy_j[nk, mk, ck],
            table.write_energy_j[nk, mk, ck],
            table.leakage_w[nk, mk, ck],
            table.area_mm2[nk, mk, ck])
        return np.asarray(obj), np.asarray(table.area_mm2[nk, mk, ck])

    def corner_info(self, ki: int, oi: int) -> dict:
        """Human-readable identity of one (point, org) cell."""
        p = self.points[ki]
        org = engine.ORGS[oi]
        return {"mem": p.mem, "capacity_mb": p.capacity_mb,
                "node": p.node.name, "org_index": oi,
                "org": f"{org.banks}b x {org.rows}r x {org.cols}c "
                       f"x {org.access}"}


def lower(problem: InverseProblem) -> Lowered:
    """Lower a problem to its static structure + traced functions."""
    spec = problem.sweep.resolve()
    points = spec.designs
    groups = bounds.leaf_groups(points)
    if not groups:
        raise ValueError(f"{problem.name}: no NVM design points — nothing "
                         "to optimize (every leaf is an MRAM device knob)")
    theta0 = bounds.pack_theta(groups)
    theta_lo, theta_hi = bounds.theta_bounds(groups)

    nodes = tuple(dict.fromkeys(p.node for p in points))
    mems = tuple(dict.fromkeys(p.mem for p in points))
    caps = tuple(dict.fromkeys(p.capacity_bytes for p in points))
    nk = np.array([nodes.index(p.node) for p in points])
    mk = np.array([mems.index(p.mem) for p in points])
    ck = np.array([caps.index(p.capacity_bytes) for p in points])

    group_index = {g.key: i for i, g in enumerate(groups)}
    const_cells, relaxed = {}, {}
    for ni, nd in enumerate(nodes):
        for mi, mem in enumerate(mems):
            key = (mem, nd.name)
            if key in group_index:
                relaxed[(ni, mi)] = group_index[key]
            elif mem == "sram":
                const_cells[(ni, mi)] = \
                    bitcell_mod.characterize(mem, nd).as_array()
            else:
                # an (NVM, node) combo no design point uses: the kernel
                # still wants a row; its outputs are never gathered
                const_cells[(ni, mi)] = np.ones(
                    len(bitcell_mod.ARRAY_FIELDS))
    cal_mat = np.array([[[getattr(calibration.get(m, nd), f)
                          for f in engine.CAL_FIELDS]
                         for m in mems] for nd in nodes])
    is_sram = np.array([m == "sram" for m in mems])
    node_mat = np.stack([engine.node_row(nd) for nd in nodes])
    n_technode = len(engine.TECHNODE_FIELDS)
    caps_arr = np.array(caps, dtype=np.int64)

    if problem.objective == "edp":
        stats = spec.scenarios
        batch = workload_engine.pack(stats)
        pmat = np.stack([np.array([getattr(p, f)
                                   for f in workload_engine.PLATFORM_FIELDS])
                         for p in spec.platforms])
    else:
        batch, pmat = None, None

    lowered = Lowered(
        problem=problem, points=points, groups=groups,
        theta0=theta0, theta_lo=theta_lo, theta_hi=theta_hi,
        area_budget_mm2=None,
        nodes=nodes, mems=mems, caps=caps, nk=nk, mk=mk, ck=ck,
        cal_mat=cal_mat, is_sram=is_sram,
        node4=np.ascontiguousarray(node_mat[:, :n_technode]),
        peri=np.ascontiguousarray(node_mat[:, n_technode:]),
        caps_arr=caps_arr, const_cells=const_cells, relaxed=relaxed,
        valid=engine.valid_mask(caps_arr)[ck],
        caps_k=np.array([float(p.capacity_bytes) for p in points]),
        batch=batch, pmat=pmat)

    budget = problem.area_budget_mm2
    if budget == "iso":
        with jax.enable_x64(True):
            _, grid_areas = lowered.grid_objective()
        budget = _iso_budget(grid_areas)
    if budget is not None:
        budget = float(budget)
    return dataclasses.replace(lowered, area_budget_mm2=budget)
