"""Show the PPA kernel's precision at one capacity column on the chip.

    python3 chipbench/tests/ppa_columns.py [cache|nocache]

Builds ``engine.design_table`` for 3 MB and 7.5 MB with 1, 2, 4 and 8
capacity columns (the extra columns are dummies 64 bytes apart), on the
16 nm node alone and on all four DTCO nodes, and prints the worst
relative error of each tuned design's PPA against the plain reference.
On a TPU v5e the single-column program returns read/write latency and
leakage at 7.5 MB with float32-level error (~2e-8) while every wider
program, and the CPU, agree to ~1e-14.  ``nocache`` compiles afresh with
the persistent compile cache off.  Needs the chip.
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


def main(mode: str) -> int:
    import jax

    from chipbench import harness
    from chipbench import reference as R
    from chipbench.compare import rel_err

    harness.tpu_devices(1)
    if mode == "cache":
        harness.enable_compile_cache()
    else:
        jax.config.update("jax_enable_compilation_cache", False)
    from repro.core import engine, tech

    for names in (NODES[:1], NODES):
        nodes = tuple(tech.node(n) for n in names)
        for cap_mb in (3.0, 7.5):
            cap = int(cap_mb * 2**20)
            for count in (1, 2, 4, 8):
                caps = (cap,) + tuple(cap + 64 * (i + 1)
                                      for i in range(count - 1))
                table = engine.design_table(MEMS, caps, nodes=nodes)
                worst: dict[str, float] = {}
                for nd in nodes:
                    for m in MEMS:
                        got = table.tuned(m, cap, node=nd)
                        want = R.design(m, cap, nd.name)
                        for f in R.DESIGN_FIELDS:
                            worst[f] = max(worst.get(f, 0.0), rel_err(
                                getattr(got, f), getattr(want, f)))
                print(f"nodes={len(nodes)} cap={cap_mb}MB columns={count}",
                      json.dumps(worst), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else "cache"))
