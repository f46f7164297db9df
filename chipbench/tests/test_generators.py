"""The mega generator's traffic and the frozen LM stream table it is
judged against.  Runs on the CPU."""

import math
import os

import pytest

from chipbench import harness
from chipbench import reference as R

TRAFFIC = ("shipped_ladder", "shipped_ladder_4chip")


def traffic(name):
    return harness.load_json(os.path.join(harness.BENCH_DIR, "traffic",
                                          f"{name}.json"))


@pytest.mark.parametrize("name", TRAFFIC)
def test_traffic_is_the_shipped_mega_sweep(name):
    from repro import scenarios
    from repro.core.tech import NODES

    t = traffic(name)
    assert [float(c) for c in t["capacities_mb"]] == \
        [float(c) for c in scenarios.MEGA_CAPACITIES_MB]
    assert t["nodes"] == list(NODES)


def test_lm_table_holds_every_lm_scenario_of_the_sweep():
    from repro import scenarios

    keys = {s.workload for s in scenarios.mega_spec().scenarios
            if "/" in s.workload}
    assert keys == set(R.lm_table())
    for key in keys:
        stats = R.lm_stats(key)
        assert stats.workload == key and stats.streams


@pytest.mark.parametrize("key", sorted(R.lm_table()))
def test_lm_table_rows_keep_the_accounting_identities(key):
    """The identities the LM byte accounting states: activations written
    are half of those read, their reuse distance is 4 x tokens x d_model
    / 64 with tokens x d_model = activations read / 24, training adds
    gradients, optimizer reads and writes of 1, 3 and 2 x the weights,
    and no stream is empty."""
    s = {a.label: a for a in R.lm_stats(key).streams}
    act_r, act_w = s["activations.r"], s["activations.w"]
    assert act_w.bytes_total == act_r.bytes_total / 2
    td = act_r.bytes_total / 24
    assert act_r.reuse_distance == act_w.reuse_distance == 4 * td // 64
    assert not act_r.is_write and act_w.is_write
    for label in ("weights", "kv.r", "kv.w", "logits", "grads.w", "opt.r",
                  "opt.w"):
        if label in s:
            assert math.isinf(s[label].reuse_distance)
    training = R.lm_stats(key).training
    assert training == key.endswith("train_4k")
    if training:
        w = s["weights"].bytes_total
        assert (s["grads.w"].bytes_total, s["opt.r"].bytes_total,
                s["opt.w"].bytes_total) == (w, 3 * w, 2 * w)
    assert all(a.bytes_total > 0 for a in s.values())

