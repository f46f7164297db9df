"""Tests for the inverse-design subsystem (repro/inverse/).

Families:

  grad       finite-difference checks (rel err <= 1e-5) of the loss
             gradient on every exposed leaf at 16 nm and 7 nm;
  cell       the relaxed soft bitcell at HARD_TEMP equals the standard
             ``characterize`` cell bit-for-bit (softmin hardening is
             exact, not approximate);
  recover    softmin -> argmin consistency: hardened center evaluation
             recovers the grid-argmin winner on the golden isocap and
             dtco_isoarea specs, same (mem, capacity, node, org) corner;
  wall       the STT scaling-wall penalty: ~0 with 16 nm overdrive
             headroom, large and finite (with finite gradients) at the
             extrapolated 2 nm node;
  solve      the end-to-end acceptance: gradient descent finds an
             off-grid design with strictly lower EDP than every grid
             corner at equal area budget, verified through the standard
             (non-relaxed) engine path at <= 1e-12 parity;
  problem    deepnvm.inverse/1 round-trip, strict unknown-field
             rejection, result-document serializability;
  sens       elasticity tables: finite, nonzero, correctly labeled.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import inverse
from repro.core import bitcell, tech
from repro.core.sweep import SymbolicSweepSpec
from repro.inverse import bounds as bounds_mod
from repro.inverse import relax, sensitivity

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SPECS = os.path.join(ROOT, "specs")

# Small two-node grid exercising both flavors at 16 nm and 7 nm: the
# gradient tests cover every leaf of all four (flavor, node) groups.
TWO_NODE_DOC = {
    "schema": "deepnvm.sweepspec/2", "name": "inv-two-node",
    "scenarios": ["cnn/alexnet/infer@b4", "cnn/resnet18/train@b64"],
    "designs": ["sram@3MB", "stt@3MB", "sot@3MB",
                "stt@3MB@7nm-scaled", "sot@3MB@7nm-scaled"],
    "platforms": ["gtx-1080ti"], "baseline_mem": "sram",
}


@pytest.fixture(scope="module")
def two_node_lowered():
    prob = inverse.InverseProblem(
        sweep=SymbolicSweepSpec.from_json(TWO_NODE_DOC), objective="edp")
    with jax.enable_x64(True):
        yield relax.lower(prob)


@pytest.fixture(scope="module")
def isocap_problem():
    return inverse.InverseProblem(
        sweep=SymbolicSweepSpec.load(os.path.join(SPECS, "isocap.json")),
        objective="edp", name="isocap-inv")


# ---------------------------------------------------------------------------
# grad: finite differences on every leaf, 16 nm and 7 nm
# ---------------------------------------------------------------------------


def test_gradient_matches_finite_differences_on_every_leaf(
        two_node_lowered):
    low = two_node_lowered
    names = [f"{g.flavor}@{g.node.name}:{f}"
             for g in low.groups for f in bounds_mod.LEAF_FIELDS]
    assert len(names) == 4 * bounds_mod.N_LEAVES  # both flavors x nodes
    # a seeded off-center point: the SOT anchor has ic0_set == ic0_reset
    # exactly, which parks min(od_set, od_reset) on its kink — a generic
    # point breaks the tie by far more than the FD step
    rng = np.random.default_rng(7)
    theta = low.theta0 + rng.uniform(-0.02, 0.02, low.theta0.size)
    with jax.enable_x64(True):
        temp = 0.5
        loss = jax.jit(low.loss)
        grad = np.asarray(jax.jit(jax.grad(low.loss))(theta, temp))
        assert np.all(np.isfinite(grad))
        h = 1e-5
        for i, name in enumerate(names):
            e = np.zeros_like(theta)
            e[i] = h
            fd = (float(loss(theta + e, temp))
                  - float(loss(theta - e, temp))) / (2.0 * h)
            scale = max(abs(fd), abs(float(grad[i])), 1e-3)
            assert abs(fd - grad[i]) / scale <= 1e-5, \
                f"{name}: fd={fd:.9e} grad={grad[i]:.9e}"


def test_gradient_is_nonzero_on_every_leaf(two_node_lowered):
    # every exposed leaf must actually steer the loss (dead axes would
    # mean a leaf that never reaches a PPA expression)
    low = two_node_lowered
    with jax.enable_x64(True):
        grad = np.asarray(jax.grad(low.loss)(low.theta0, 0.5))
    assert np.count_nonzero(grad) == grad.size


# ---------------------------------------------------------------------------
# cell: hardened soft cell == standard characterization, bit for bit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("flavor", ["stt", "sot"])
@pytest.mark.parametrize("node", [tech.TECH_16NM,
                                  tech.scaled_node(7e-9)])
def test_hard_soft_cell_matches_characterize(flavor, node):
    # at HARD_TEMP the softmax weights are exactly one-hot, so the only
    # discrepancy vs the standard cell is the exp(ln(anchor)) round-trip
    # of the theta packing: a few ulps per component, nothing more
    groups = bounds_mod.leaf_groups([(flavor, 3 << 20, node)])
    theta = bounds_mod.pack_theta(groups)
    with jax.enable_x64(True):
        cell, od_best = relax.soft_cell(jnp.asarray(theta), groups[0],
                                        relax.HARD_TEMP)
        cell = np.asarray(cell)
    want = bitcell.characterize(flavor, node).as_array()
    assert float(od_best) > 0.0
    np.testing.assert_allclose(cell, want, rtol=1e-13)


def _largest_float(closed, *args) -> float:
    """Evaluate a closed jaxpr eqn by eqn (into nested jit calls) and
    return the largest finite magnitude any float intermediate takes."""
    from jax.extend import core as jcore

    largest = 0.0

    def run(jaxpr, consts, *vals):
        nonlocal largest
        env = dict(zip(jaxpr.constvars, consts))
        env.update(zip(jaxpr.invars, vals))

        def read(v):
            return v.val if isinstance(v, jcore.Literal) else env[v]
        for eqn in jaxpr.eqns:
            ins = [read(v) for v in eqn.invars]
            sub = eqn.params.get("jaxpr")
            if eqn.primitive.name in ("jit", "pjit") and sub is not None:
                outs = run(sub.jaxpr, sub.consts, *ins)
            else:
                outs = eqn.primitive.bind(*ins, **eqn.params)
                if not eqn.primitive.multiple_results:
                    outs = [outs]
            for o in outs:
                a = np.asarray(o)
                if a.dtype.kind == "f" and a.size:
                    fin = np.abs(a[np.isfinite(a)])
                    largest = max(largest, float(fin.max(initial=0.0)))
            env.update(zip(eqn.outvars, outs))
        return [read(v) for v in jaxpr.outvars]

    run(closed.jaxpr, closed.consts, *args)
    return largest


@pytest.mark.parametrize("flavor", ["stt", "sot"])
@pytest.mark.parametrize("node", [tech.TECH_16NM,
                                  tech.scaled_node(7e-9)])
def test_soft_cell_gradient_stays_in_float32_exponent_range(flavor, node):
    # the TPU emulates float64 in float32's exponent range: a masked
    # (write-infeasible) fin assignment must not overflow it in the
    # forward or the backward pass, or 0 * inf turns the gradient to NaN
    groups = bounds_mod.leaf_groups([(flavor, 3 << 20, node)])
    theta = jnp.asarray(bounds_mod.pack_theta(groups))

    def f(th):
        cell, od_best = relax.soft_cell(th, groups[0], 0.5)
        return jnp.log(cell).sum() + od_best

    with jax.enable_x64(True):
        closed = jax.make_jaxpr(jax.value_and_grad(f))(theta)
        largest = _largest_float(closed, theta)
        _, grad = jax.value_and_grad(f)(theta)
    assert np.isfinite(np.asarray(grad)).all()
    assert largest < float(np.finfo(np.float32).max)


# ---------------------------------------------------------------------------
# recover: golden-spec softmin -> argmin consistency
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec_name", ["isocap.json", "dtco_isoarea.json"])
def test_center_recovery_matches_grid_argmin(spec_name):
    prob = inverse.InverseProblem(
        sweep=SymbolicSweepSpec.load(os.path.join(SPECS, spec_name)),
        objective="edp", name=spec_name)
    with jax.enable_x64(True):
        low = relax.lower(prob)
        grid = inverse.grid_argmin(prob, low)
        rec = inverse.recover_corner(prob, low)
    assert rec["corner"] == grid["corner"]
    assert rec["value"] == pytest.approx(grid["value"], rel=1e-12)


# ---------------------------------------------------------------------------
# wall: the scaling-wall penalty at 16 nm vs the extrapolated 2 nm node
# ---------------------------------------------------------------------------


def test_scaling_wall_penalty_regression_at_2nm():
    n2 = tech.scaled_node(2e-9, allow_extrapolation=True)
    g2 = bounds_mod.leaf_groups([("stt", 3 << 20, n2)])[0]
    g16 = bounds_mod.leaf_groups([("stt", 3 << 20, tech.TECH_16NM)])[0]
    with jax.enable_x64(True):
        _, od2 = relax.soft_cell(
            jnp.asarray(bounds_mod.pack_theta((g2,))), g2, 0.5)
        _, od16 = relax.soft_cell(
            jnp.asarray(bounds_mod.pack_theta((g16,))), g16, 0.5)

        def penalty(od):
            return float(relax.LAMBDA_WALL
                         * jax.nn.softplus(-od / relax.WALL_SCALE))

        # 2 nm STT is past the wall (negative best overdrive): large,
        # finite penalty; 16 nm has headroom: near-zero penalty
        assert float(od2) < 0.0 < float(od16)
        assert penalty(od2) > 5.0
        assert penalty(od16) < 1.0
        assert np.isfinite(penalty(od2))

        # the wall is differentiable at 2 nm: the optimizer can feel it
        def wall_loss(theta):
            _, od = relax.soft_cell(theta, g2, 0.5)
            return relax.LAMBDA_WALL * jax.nn.softplus(
                -od / relax.WALL_SCALE)

        grad = np.asarray(jax.grad(wall_loss)(
            jnp.asarray(bounds_mod.pack_theta((g2,)))))
        assert np.all(np.isfinite(grad))
        assert np.any(grad != 0.0)


# ---------------------------------------------------------------------------
# solve: the off-grid acceptance (strict win + standard-path parity)
# ---------------------------------------------------------------------------


def test_solve_beats_every_grid_corner_at_equal_area(isocap_problem):
    import dataclasses
    prob = dataclasses.replace(isocap_problem, starts=1, iters=60)
    res = inverse.solve(prob)
    # strictly lower EDP than the best grid corner (hence every corner)
    # under the same iso-area budget
    assert res.best_value < res.grid_best_value
    assert res.gain_vs_grid > 0.0
    assert res.area_mm2 <= res.area_budget_mm2 * (1.0 + 1e-9)
    # the relaxed optimum is backed by the standard (non-relaxed) path
    assert res.parity_rel_err <= 1e-12
    assert res.standard_value == pytest.approx(res.best_value, rel=1e-12)
    # the converged leaves moved off the grid anchors
    anchors = {g.key: dict(zip(bounds_mod.LEAF_FIELDS, g.centers))
               for g in relax.lower(prob).groups}
    moved = [f for key, leaves in res.leaves.items()
             for f, v in leaves.items()
             if abs(v - anchors[key][f]) / anchors[key][f] > 1e-3]
    assert moved, "solver returned the anchor design"
    # result document is JSON-serializable
    json.dumps(res.to_doc())
    assert "inverse" in res.summary()


def test_target_mode_drives_objective_to_target(two_node_lowered):
    # target-hitting: ask for an EDP 10% above the center value and check
    # the loss is the squared log residual (zero iff on target)
    low = two_node_lowered
    with jax.enable_x64(True):
        import dataclasses
        obj, area, _ = low.objective_matrix(low.theta0)
        ki, oi = low.masked_argmin(np.asarray(obj), np.asarray(area))
        target = float(np.asarray(obj)[ki, oi]) * 1.1
        prob_t = dataclasses.replace(low.problem, target=target,
                                     area_budget_mm2=None)
        low_t = relax.lower(prob_t)
        loss_t = float(low_t.loss(low_t.theta0, relax.HARD_TEMP))
        # the loss is the squared log residual of the softmin objective
        # vs the target plus the (theta-only) scaling-wall penalties
        soft = float(np.asarray(obj)[ki, oi])
        wall = float(low_t.wall_penalty(low_t.theta0))
        want = (np.log(soft) - np.log(target)) ** 2 + wall
        assert loss_t >= 0.0
        assert loss_t == pytest.approx(want, rel=1e-6)


# ---------------------------------------------------------------------------
# problem: schema round-trip and strictness
# ---------------------------------------------------------------------------


def test_problem_document_round_trip(isocap_problem):
    prob = isocap_problem
    back = inverse.InverseProblem.from_json(prob.to_json())
    assert back == prob
    assert prob.to_doc()["schema"] == inverse.SCHEMA


def test_problem_rejects_unknown_fields(isocap_problem):
    doc = isocap_problem.to_doc()
    doc["unknown_knob"] = 1
    with pytest.raises(ValueError, match="unknown_knob"):
        inverse.InverseProblem.from_json(doc)
    with pytest.raises(ValueError, match="schema"):
        inverse.InverseProblem.from_json({"schema": "bogus"})


def test_problem_validates_fields(isocap_problem):
    import dataclasses
    with pytest.raises(ValueError, match="objective"):
        dataclasses.replace(isocap_problem, objective="power")
    with pytest.raises(ValueError, match="area_budget"):
        dataclasses.replace(isocap_problem, area_budget_mm2="huge")
    with pytest.raises(ValueError, match="temp"):
        dataclasses.replace(isocap_problem, temp_lo=0.0)


def test_shipped_inverse_spec_loads_and_lowers():
    prob = inverse.InverseProblem.load(
        os.path.join(SPECS, "inverse_isocap.json"))
    assert prob.objective == "edp"
    assert prob.area_budget_mm2 == "iso"
    with jax.enable_x64(True):
        low = relax.lower(prob)
    assert low.area_budget_mm2 > 0.0
    assert {g.key[0] for g in low.groups} == {"stt", "sot"}


# ---------------------------------------------------------------------------
# sens: elasticity tables
# ---------------------------------------------------------------------------


def test_sensitivity_rows_shape_and_finiteness(two_node_lowered):
    low = two_node_lowered
    rows = sensitivity.sensitivity_rows(low.problem, low)
    # 1 platform x 2 scenarios x 4 NVM points x 8 leaves
    assert len(rows) == 1 * 2 * 4 * bounds_mod.N_LEAVES
    for r in rows:
        assert np.isfinite(r["elasticity"])
        assert r["leaf"] in bounds_mod.LEAF_FIELDS
        assert r["mem"] in ("stt", "sot")
    # the headline ranking has one entry per (node, mem)
    top = sensitivity.top_knobs(rows, n=1)
    assert len(top) == 4
    assert all(abs(t["mean_elasticity"]) > 0.0 for t in top)
