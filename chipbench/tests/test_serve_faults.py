"""The serve/ cell with its shared prefix planted as streaming comes out
not correct: the builder's ``mla.lat.r.shared`` stream gets an infinite
reuse distance (always misses) in place of one sequence's latent scan.
Only the DRAM transactions of the scenarios with a shared prefix move,
and of those only at capacities near or above the scan; the check reads
every [scenario, design] of one sweep, so it must catch it.  Unbroken,
the same run is correct (``test_faults.py`` also runs it sound).

    python -m pytest chipbench/tests/test_serve_faults.py     # CPU
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import math
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "kimi_linear_decode"


@contextlib.contextmanager
def shared_prefix_streams():
    from repro.core import lm_serve

    decode_step = lm_serve.decode_step

    def broken(*args, **kwargs):
        stats = decode_step(*args, **kwargs)
        streams = tuple(dataclasses.replace(s, reuse_distance=math.inf)
                        if s.label == "mla.lat.r.shared" else s
                        for s in stats.streams)
        return dataclasses.replace(stats, streams=streams)

    lm_serve.decode_step = broken
    try:
        yield
    finally:
        lm_serve.decode_step = decode_step


def run_faulted() -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.abspath(__file__)],
                         env=env, capture_output=True, text=True,
                         timeout=600, check=True)
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_shared_prefix_as_streaming_is_not_correct():
    result = run_faulted()
    assert result["correct"] is False, result["checks"]
    err = result["checks"]["max_rel_err"]
    assert err["value"] > err["limit"]
    assert result["checks"]["winners_differing"]["value"] == 0


def main() -> int:
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from chipbench import harness
    from chipbench.tests import faulted_run

    with shared_prefix_streams():
        result, _ = harness.run_cell(CELL, faulted_run.SEED,
                                     faulted_run.SECONDS, False,
                                     check_device=False,
                                     traffic=faulted_run.small_traffic(CELL))
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
