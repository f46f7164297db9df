"""Show that served studies with one capacity inherit the PPA kernel's
one-column precision loss on the chip (see ``ppa_columns.py``).

    python3 chipbench/tests/served_one_column.py [chip|cpu]

Resolves an iso-capacity study (10 CNN scenarios x 3 memories at one
capacity), the same across the four DTCO nodes, and a scalability study
(three capacities), each at 3 MB and at 7.5 MB, and evaluates each twice:
through the service's capacity-bucketed path (``service.evaluate_spec``)
and the exact ``sweep.run``.  Prints, per study and path, the worst
relative error of the tuned designs' PPA and of the cells' runtime and
energies against the plain reference.  On a TPU v5e the one-capacity
studies at 7.5 MB read ~1e-8 to ~1e-7 on both paths while the
three-capacity study, and every study on the CPU (``cpu``, the second
witness), read ~1e-14.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

NODES = ("16nm-finfet", "12nm-scaled", "10nm-scaled", "7nm-scaled")
MEMS = ("sram", "stt", "sot")
CNNS = ("alexnet", "googlenet", "vgg16", "resnet18", "squeezenet")
CELLS = (("runtime_s", "runtime"), ("dyn_j", "dyn"), ("leak_j", "leak"),
         ("energy_j", "energy"), ("edp_js", "edp"))


def studies(cap_mb: float) -> dict[str, dict]:
    scen = [f"cnn/{w}/{s}" for w in CNNS for s in ("infer@b4", "train@b64")]

    def doc(name, designs):
        return {"schema": "deepnvm.sweepspec/2", "name": name,
                "scenarios": scen, "designs": designs,
                "platforms": ["gtx-1080ti"], "baseline_mem": "sram"}

    return {
        "isocap": doc("isocap", [f"{m}@{cap_mb:g}MB" for m in MEMS]),
        "dtco": doc("dtco", [f"{m}@{cap_mb:g}MB@{n}" for n in NODES
                             for m in MEMS]),
        "scalability": doc("scalability", [
            f"{m}@{c:g}MB" for c in (cap_mb / 2, cap_mb, cap_mb * 2)
            for m in MEMS]),
    }


def main(where: str) -> int:
    from chipbench import harness
    from chipbench import reference as R
    from chipbench.compare import ErrorTable

    if where == "chip":
        harness.tpu_devices(1)
        harness.enable_compile_cache()
    import jax

    from repro.core import sweep
    from repro.sweep import service

    print(f"device {jax.devices()[0].device_kind}", flush=True)
    for cap_mb in (3.0, 7.5):
        for kind, doc in studies(cap_mb).items():
            spec = sweep.SymbolicSweepSpec.from_json(doc).resolve()
            for path, evaluate in (("bucketed", service.evaluate_spec),
                                   ("exact", sweep.run)):
                res = evaluate(spec)
                errs = ErrorTable()
                for p, got in zip(spec.designs, res.designs):
                    want = R.design(p.mem, p.capacity_bytes, p.node.name)
                    errs.winner(p.mem, got.org, want.org)
                    for f in R.DESIGN_FIELDS:
                        errs.add(f, getattr(got, f), getattr(want, f))
                tensors = {f: res.metric(m, include_dram=False)
                           for f, m in CELLS}
                for si, s in enumerate(spec.scenarios):
                    stage = "train" if s.training else "infer"
                    stats = R.cnn_stats(f"cnn/{s.workload}/{stage}@b{s.batch}")
                    for di, p in enumerate(spec.designs):
                        ref = R.cell(stats, p.mem, p.capacity_bytes,
                                     p.node.name, spec.platforms[0].name)
                        for f, _ in CELLS:
                            errs.add(f, tensors[f][0, si, di], ref[f])
                print(f"{kind}@{cap_mb:g}MB {path}", json.dumps(
                    {"max_rel_err": errs.max_err(),
                     "winners_differing": len(errs.winners_differing),
                     "worst": errs.worst}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else "chip"))
