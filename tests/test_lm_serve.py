"""Tests for the serve/ namespace: decode-step traffic of a KDA + MLA + MoE
stack with finite reuse (core/lm_serve.py, scenarios.serve_traffic).

  reference  the program's streams equal those that
             chipbench/reference/lm_serve.py builds from the configuration
             document, at the published widths (every scenario of
             specs/kimi_linear_serve.json) and at the reduced config;
             folded cells and DRAM transactions equal ``reference.cell``;
  naming     resolve/name_of round-trip the spec's names, bad names raise;
  config     every width of ``config()`` is the published config's;
  unchanged  the lm/ scenarios still match the frozen stream table and the
             mega spec keeps its axes;
  cachesim   one reduced decode step lowered to a block trace in the
             modelled kernel order: the shared prefix's simulated misses
             fall with capacity and sit on the analytic curve's side of
             50 % below and above one sequence's latent scan.
"""

import json
import math
import os

import pytest

import repro.configs as configs
from chipbench import reference as R
from chipbench.reference import lm_serve as R_serve
from repro import scenarios
from repro.configs import kimi_linear_48b_a3b
from repro.core import cachesim, lm_serve, sweep
from repro.core.sweep import SymbolicSweepSpec
from repro.core.traffic import TrafficStats

ROOT = os.path.join(os.path.dirname(__file__), "..")
SPEC = os.path.join(ROOT, "specs", "kimi_linear_serve.json")
CONFIG = os.path.join(ROOT, "chipbench", "configs", "kimi_linear_serve.json")
ARCH = "kimi-linear-48b-a3b"
REL = 1e-12


def published() -> dict:
    with open(CONFIG) as f:
        return json.load(f)


def reduced_doc() -> dict:
    """``kimi_linear_48b_a3b.reduced_config()`` as a configuration
    document, written out by hand in the published config's keys."""
    return {
        "hidden_size": 64, "num_hidden_layers": 4, "num_attention_heads": 4,
        "vocab_size": 256, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "q_lora_rank": None,
        "num_experts": 8, "num_experts_per_token": 2,
        "moe_intermediate_size": 32, "num_shared_experts": 1,
        "first_k_dense_replace": 1, "intermediate_size": 128,
        "linear_attn_config": {"kda_layers": [1, 2, 3],
                               "full_attn_layers": [4], "num_heads": 4,
                               "head_dim": 16, "short_conv_kernel_size": 4},
        "assumed_sizes": {"kda_gate_rank": 16, "weight_bytes": 2,
                          "cache_bytes": 2, "kda_state_bytes": 4},
    }


def spec_names() -> tuple[str, ...]:
    return SymbolicSweepSpec.load(SPEC).scenarios


def close(got: float, want: float) -> bool:
    if math.isinf(want):
        return got == want
    return abs(got - want) <= REL * abs(want)


def assert_same_streams(got: TrafficStats, want) -> None:
    assert [s.label for s in got.streams] == [s.label for s in want.streams]
    for g, w in zip(got.streams, want.streams):
        assert (g.is_write, g.writeback) == (w.is_write, w.writeback), g.label
        assert close(g.bytes_total, w.bytes_total), g.label
        assert close(g.reuse_distance, w.reuse_distance), g.label
    assert close(got.macs_per_batch, want.macs_per_batch)
    assert (got.batch, got.training) == (want.batch, want.training)


REDUCED_STEPS = [(c, b, p) for c in (16, 64, 400) for b in (1, 2, 8, 33)
                 for p in (0, 25, 75)]


def reduced_stats(c, b, p) -> TrafficStats:
    return lm_serve.decode_step(kimi_linear_48b_a3b.reduced_config(), c, b,
                                p, f"reduced@c{c}@b{b}@p{p}")


# ---------------------------------------------------------------------------
# reference: streams, then folded cells
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", spec_names())
def test_published_streams_equal_the_reference(name):
    assert_same_streams(scenarios.resolve(name),
                        R_serve.stats(published(), name))


@pytest.mark.parametrize("c,b,p", REDUCED_STEPS)
def test_reduced_streams_equal_the_reference(c, b, p):
    assert_same_streams(reduced_stats(c, b, p),
                        R_serve.decode_step(reduced_doc(), c, b, p))


def test_stream_table_at_the_published_widths():
    """Spot values of the stream table: 8 routed experts touched per MoE
    layer at batch 1 and ~163 at 32, the shared prefix re-read at one
    sequence's scan (c x 1,152 B), everything else one step later."""
    s = {a.label: a for a in scenarios.resolve(
        f"serve/{ARCH}/decode@c32768@b32@p75").streams}
    step = sum(a.bytes_total for a in s.values())
    expert = 3 * 2304 * 1024 * 2
    assert s["w.routed"].bytes_total == pytest.approx(
        26 * 256 * (1 - (31 / 32) ** 32) * expert, rel=REL)
    assert lm_serve.experts_touched(256, 8, 1) == pytest.approx(8.0)
    assert round(lm_serve.experts_touched(256, 8, 32)) == 163
    assert s["mla.lat.r.shared"].reuse_distance == 32768 * 1152
    assert s["mla.lat.r.shared"].bytes_total == 7 * 1152 * 31 * 24576
    assert s["kda.state.r"].bytes_total == 32 * 20 * 32 * 128 ** 2 * 4
    for label in ("w.kda", "w.mla", "w.dense", "w.shared", "w.router",
                  "w.routed", "w.head", "kda.state.r", "kda.state.w",
                  "kda.conv.r", "kda.conv.w", "mla.lat.r", "mla.lat.w"):
        assert s[label].reuse_distance == pytest.approx(step, rel=REL)
    assert math.isinf(s["logits"].reuse_distance)
    # no shared prefix at batch 1 or p = 0: the zero-byte stream is dropped
    for name in (f"serve/{ARCH}/decode@c4096@b1@p0",
                 f"serve/{ARCH}/decode@c4096@b8@p0"):
        labels = [a.label for a in scenarios.resolve(name).streams]
        assert "mla.lat.r.shared" not in labels and len(labels) == 16


def fold_against_reference(spec: sweep.SweepSpec, ref_of) -> float:
    """Worst relative error of the folded cells and DRAM transactions of
    ``spec`` against ``reference.cell`` on the reference's streams."""
    res = sweep.run(spec)
    worst = 0.0
    metrics = {f: res.metric(m, include_dram=False)
               for f, m in (("runtime_s", "runtime"), ("dyn_j", "dyn"),
                            ("leak_j", "leak"), ("energy_j", "energy"),
                            ("edp_js", "edp"))}
    for si, s in enumerate(spec.scenarios):
        ref_stats = ref_of(s)
        for di, p in enumerate(spec.designs):
            want = R.cell(ref_stats, p.mem, p.capacity_bytes, p.node.name,
                          spec.platforms[0].name)
            for field, got in metrics.items():
                worst = max(worst, abs(got[0, si, di] - want[field])
                            / abs(want[field]))
            want_tx = ref_stats.dram_tx(p.capacity_bytes)
            worst = max(worst, abs(res.tables[0].dram_tx[si, di] - want_tx)
                        / want_tx)
    return worst


@pytest.fixture(scope="module")
def designs():
    from repro.core.tech import NODES
    return sweep.design_grid(sweep.MEMS, (0.5, 5, 48, 96),
                             nodes=(NODES["16nm-finfet"], NODES["7nm-scaled"]))


def test_published_cells_equal_the_reference(designs):
    from repro.core.tech import TPU_V5E
    cfg = published()
    spec = SymbolicSweepSpec.load(SPEC).resolve()
    spec = sweep.SweepSpec(name="kimi-cells", scenarios=spec.scenarios,
                           designs=designs, platforms=(TPU_V5E,))
    assert fold_against_reference(
        spec, lambda s: R_serve.stats(cfg, s.workload)) <= REL


def test_reduced_cells_equal_the_reference(designs):
    from repro.core.tech import TPU_V5E
    steps = [(64, 8, 75), (400, 33, 25), (16, 1, 0)]
    spec = sweep.SweepSpec(name="kimi-reduced-cells",
                           scenarios=tuple(reduced_stats(*s) for s in steps),
                           designs=designs, platforms=(TPU_V5E,))
    by_key = {f"reduced@c{c}@b{b}@p{p}": (c, b, p) for c, b, p in steps}
    assert fold_against_reference(
        spec, lambda s: R_serve.decode_step(reduced_doc(),
                                            *by_key[s.workload])) <= REL


# ---------------------------------------------------------------------------
# naming
# ---------------------------------------------------------------------------


def test_spec_names_round_trip_and_fold_the_serve_grid():
    names = spec_names()
    assert len(names) == len(set(names)) == 38
    for name in names:
        assert scenarios.name_of(scenarios.resolve(name)) == name
    spec = SymbolicSweepSpec.load(SPEC).resolve()
    assert sweep.n_cells(spec) == 38 * 288 == 10_944
    assert SymbolicSweepSpec.from_spec(spec).scenarios == names
    pairs = {(int(n.split("@c")[1].split("@")[0]),
              int(n.split("@b")[1].split("@")[0])) for n in names}
    assert len(pairs) == 23 and all(b * c <= 4_194_304 for c, b in pairs)


@pytest.mark.parametrize("name", [
    f"serve/{ARCH}/decode@c04096@b8@p75",        # not canonical
    f"serve/{ARCH}/prefill@c4096@b8@p75",
    f"serve/{ARCH}/decode@c4096@b8",
    f"serve/{ARCH}/decode@c4096@b0@p0",
    f"serve/{ARCH}/decode@c4096@b8@p100",
    f"serve/{ARCH}/decode@c4097@b8@p75",         # fractional prefix
    "serve/deepseek-v3-671b/decode@c4096@b8@p75",  # not a serve arch
])
def test_bad_serve_names_raise(name):
    with pytest.raises(ValueError):
        scenarios.resolve(name)


# ---------------------------------------------------------------------------
# config: the published widths
# ---------------------------------------------------------------------------


def test_config_widths_are_the_published_ones():
    doc, cfg = published(), kimi_linear_48b_a3b.config()
    la, mla, moe = cfg.linear_attn, cfg.mla, cfg.moe
    pairs = [
        (cfg.n_layers, doc["num_hidden_layers"]),
        (cfg.d_model, doc["hidden_size"]),
        (cfg.n_heads, doc["num_attention_heads"]),
        (cfg.n_kv_heads, doc["num_key_value_heads"]),
        (cfg.head_dim, doc["head_dim"]),
        (cfg.d_ff, doc["intermediate_size"]),
        (cfg.vocab, doc["vocab_size"]),
        (cfg.rope_theta, doc["rope_theta"]),
        (mla.q_lora_rank, doc["q_lora_rank"] or 0),
        (mla.kv_lora_rank, doc["kv_lora_rank"]),
        (mla.qk_nope_dim, doc["qk_nope_head_dim"]),
        (mla.qk_rope_dim, doc["qk_rope_head_dim"]),
        (mla.v_head_dim, doc["v_head_dim"]),
        (moe.n_experts, doc["num_experts"]),
        (moe.top_k, doc["num_experts_per_token"]),
        (moe.d_expert, doc["moe_intermediate_size"]),
        (moe.n_shared, doc["num_shared_experts"]),
        (moe.first_dense_layers, doc["first_k_dense_replace"]),
        (moe.dense_d_ff, doc["intermediate_size"]),
        (list(la.kda_layers), doc["linear_attn_config"]["kda_layers"]),
        (list(la.full_attn_layers),
         doc["linear_attn_config"]["full_attn_layers"]),
        (la.n_heads, doc["linear_attn_config"]["num_heads"]),
        (la.head_dim, doc["linear_attn_config"]["head_dim"]),
        (la.conv_kernel, doc["linear_attn_config"]["short_conv_kernel_size"]),
        (la.gate_rank, doc["assumed_sizes"]["kda_gate_rank"]),
    ]
    for got, want in pairs:
        assert got == want
    assert doc["reduced"] == []
    assert sorted(la.kda_layers + la.full_attn_layers) == \
        list(range(1, cfg.n_layers + 1))
    # ~48B parameters in all, ~3B touched per token
    n = lm_serve.param_counts(cfg)
    whole = sum(v for k, v in n.items() if k != "expert") \
        + 26 * 256 * n["expert"]
    active = sum(v for k, v in n.items() if k != "expert") \
        + 26 * 8 * n["expert"]
    assert 47e9 < whole < 50e9 and 2.8e9 < active < 3.4e9


def test_serve_arch_stays_out_of_the_lm_study():
    assert ARCH not in configs.ARCH_IDS and ARCH not in configs.all_archs()
    assert not any(n.startswith("serve/") for n in scenarios.names())


# ---------------------------------------------------------------------------
# unchanged: the lm/ namespace and the mega spec
# ---------------------------------------------------------------------------


def test_lm_scenarios_still_match_the_frozen_table():
    table = R.lm_table()
    lm = [s for s in scenarios.mega_spec().scenarios if "/" in s.workload]
    assert len(lm) == 32 and {s.workload for s in lm} == set(table)
    for s in lm:
        row = table[s.workload]
        assert (s.batch, s.training, s.macs_per_batch) == \
            (row["batch"], row["training"], row["macs_per_batch"])
        assert [(a.label, a.bytes_total, a.is_write,
                 None if math.isinf(a.reuse_distance) else a.reuse_distance,
                 a.writeback) for a in s.streams] == \
            [tuple(r) for r in row["streams"]]


def test_mega_spec_keeps_its_axes():
    spec = scenarios.mega_spec()
    assert len(spec.scenarios) == 182
    assert sweep.n_cells(spec) == 104_832


# ---------------------------------------------------------------------------
# cachesim: the shared prefix's miss curve from a block trace
# ---------------------------------------------------------------------------


def step_trace(cfg, c: int, b: int, p: int) -> tuple[list, list, int]:
    """One decode step of ``cfg`` lowered to a block trace in the modelled
    kernel order (``lm_serve`` docstring), one block per token latent
    (``lat`` bytes).  Returns the block ids, each access's stream label,
    and the block size."""
    la, mla, moe = cfg.linear_attn, cfg.mla, cfg.moe
    lat = int((mla.kv_lora_rank + mla.qk_rope_dim) * lm_serve.CACHE_BYTES)
    params = lm_serve.param_counts(cfg)
    prefix = c * p // 100
    blocks: list[int] = []
    labels: list[str] = []
    regions: dict = {}

    def region(key, nbytes):
        if key not in regions:
            start = sum(n for _, n in regions.values())
            regions[key] = (start, max(1, math.ceil(nbytes / lat)))
        return regions[key]

    def touch(key, nbytes, label):
        start, n = region(key, nbytes)
        blocks.extend(range(start, start + n))
        labels.extend([label] * n)

    kda_w = params["kda"] / len(la.kda_layers) * lm_serve.WEIGHT_BYTES
    mla_w = params["mla"] / len(la.full_attn_layers) * lm_serve.WEIGHT_BYTES
    state = la.n_heads * la.head_dim ** 2 * lm_serve.STATE_BYTES
    conv = (la.conv_kernel - 1) * 3 * la.n_heads * la.head_dim \
        * lm_serve.CACHE_BYTES
    expert = params["expert"] * lm_serve.WEIGHT_BYTES
    touched = round(lm_serve.experts_touched(moe.n_experts, moe.top_k, b))
    for layer in range(1, cfg.n_layers + 1):
        if layer in la.kda_layers:
            touch(("w.kda", layer), kda_w, "w.kda")
            for seq in range(b):
                touch(("conv", layer, seq), conv, "kda.conv.r")
                touch(("state", layer, seq), state, "kda.state.r")
                touch(("state", layer, seq), state, "kda.state.w")
                touch(("conv", layer, seq), conv, "kda.conv.w")
        else:
            touch(("w.mla", layer), mla_w, "w.mla")
            for seq in range(b):
                for t in range(prefix):
                    touch(("lat", layer, "prefix", t), lat,
                          "mla.lat.r" if seq == 0 else "mla.lat.r.shared")
                for t in range(prefix, c):
                    touch(("lat", layer, seq, t), lat, "mla.lat.r")
                touch(("lat", layer, seq, c), lat, "mla.lat.w")
        if layer <= moe.first_dense_layers:
            touch(("w.dense", layer), 3 * cfg.d_model * moe.dense_d_ff
                  * lm_serve.WEIGHT_BYTES, "w.dense")
        else:
            touch(("w.router", layer), cfg.d_model * moe.n_experts
                  * lm_serve.WEIGHT_BYTES, "w.router")
            touch(("w.shared", layer), expert * moe.n_shared, "w.shared")
            for e in range(touched):
                touch(("w.expert", layer, e), expert, "w.routed")
    touch(("w.head",), params["head"] * lm_serve.WEIGHT_BYTES, "w.head")
    return blocks, labels, lat


def test_shared_prefix_misses_follow_the_analytic_curve():
    cfg = kimi_linear_48b_a3b.reduced_config()
    c, b, p = 64, 4, 75
    blocks, labels, lat = step_trace(cfg, c, b, p)
    distances = cachesim.stack_distance_profile(blocks)
    shared = [d for d, label in zip(distances, labels)
              if label == "mla.lat.r.shared"]
    stream = {s.label: s for s in lm_serve.decode_step(
        cfg, c, b, p, "trace").streams}["mla.lat.r.shared"]
    # the trace holds the stream's bytes, one block per token latent
    assert len(shared) * lat == stream.bytes_total
    ladder = [c // 8, c // 4, c // 2, c, 2 * c, 4 * c, 8 * c]
    misses = [cachesim.misses_at_capacity(shared, n) for n in ladder]
    assert all(a >= b for a, b in zip(misses, misses[1:])), misses
    assert misses[0] == len(shared) and misses[-1] == 0

    def analytic(capacity_blocks: int) -> float:
        alone = TrafficStats("shared", b, False, (stream,), 0.0)
        return alone.dram_tx(capacity_blocks * lat) / alone.l2_read_tx

    # one capacity below one sequence's latent scan (c blocks), one above
    for capacity in (c // 2, 4 * c):
        simulated = cachesim.misses_at_capacity(shared, capacity) \
            / len(shared)
        assert (simulated > 0.5) == (analytic(capacity) > 0.5), capacity
    assert analytic(c // 2) > 0.5 > analytic(4 * c)
