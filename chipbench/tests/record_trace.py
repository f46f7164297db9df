"""Record the small device trace that ``test_trace.py`` reduces.

    python3 chipbench/tests/record_trace.py OUT.json

Runs a few PPA-kernel and fold calls on the chip under the profiler,
inside the benchmark's window annotation and host spans, and writes the
planes ``trace.planes_from_profile`` keeps as JSON.  Needs the chip.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(out: str) -> int:
    import jax

    from chipbench import harness, spans, trace

    harness.tpu_devices(1)
    harness.enable_compile_cache()
    from repro import scenarios
    from repro.core import engine, tech, workload_engine

    nodes = tuple(tech.node(n) for n in ("16nm-finfet", "7nm-scaled"))
    stats = [scenarios.resolve(f"cnn/alexnet/{s}@b{b}")
             for s, b in (("infer", 4), ("train", 64))]

    def once(k: int):
        caps = tuple((3 << 20) + 64 * (i + 7 * k) for i in range(4))
        table = engine.design_table(("sram", "stt", "sot"), caps, nodes=nodes)
        designs = [table.tuned(m, c, node=nodes[0])
                   for m in ("sram", "stt", "sot") for c in caps]
        workload_engine.evaluate_bucketed(stats, designs)

    once(0)                            # compile outside the trace
    spans.install()
    d = tempfile.mkdtemp(prefix="chipbench-record-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        for k in range(1, 4):
            once(k)
            time.sleep(0.02)
    jax.profiler.stop_trace()
    spans.uninstall()
    planes = trace.load_dir(d)
    with open(out, "w") as f:
        json.dump(planes, f)
    print(json.dumps(trace.reduce(planes, [0])))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
