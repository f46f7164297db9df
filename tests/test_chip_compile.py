"""The main path's device programs, compiled for a described TPU v5e.

Nothing runs: each case lowers a jitted program at its real shapes against
the v5e:2x2 topology and compiles it with the TPU compiler, which refuses
what the chip would refuse (unsupported float64 ops, unaligned Pallas
slices, programs that do not fit).  Cases:

  ppa        ``engine._ppa_kernel``, both ``anchor_peri`` traces, at the
             mega-sweep's 4 nodes x 3 mems x 24 capacities x 288 orgs;
  fold       ``workload_engine._fold`` jitted as it stands, and the packed
             ``_fold_packed`` that the sweeps and the service call, each at
             the mega-sweep chunk shape and at the golden isocap spec's
             bucketed shape;
  sharded    the ``shard_map``'d fold on a 4-chip sweep mesh, which must
             hold no cross-chip collective (the fold has no cross-chunk
             terms) and returns each chunk's packed buffer as one row;
  pallas     the flash-attention and WKV6 kernels at the shapes
             ``kernels/ops.py`` hands them on the TPU.

The topology is described inside a module fixture (only one process may
load the TPU library at a time), and each case turns the persistent
compilation cache off around itself: a program compiled for a described
chip is written to the cache but cannot be read back without one.  The
engine cases compile under ``jax.enable_x64(True)``, as the engine runs;
the Pallas kernels are bf16/f32 model kernels called without x64 (under
x64 their int64 index arithmetic does not lower for the TPU).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from repro import scenarios
from repro.core import engine, workload_engine
from repro.core.sweep import SymbolicSweepSpec

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
# the mega-sweep's fold chunk (s, k, d, p) and its packed output's length:
# 2 [s] + 3 [s, d] + 5 [p, s, d] float64 values
MEGA_CHUNK = (8, 1024, 32, 2)
PACKED_N = 3344


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no TPU here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """The persistent compilation cache off, for one case."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture
def chip_compile(no_compile_cache):
    """An engine case: x64 on, the persistent compilation cache off."""
    with jax.enable_x64(True):
        yield


def _shapes(sharding, *specs):
    return [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in specs]


def _fold_args(sharding, s, k, d, p, lead=(), pmat_sharding=None):
    """The fold's 13 arguments; ``lead`` prepends a chunk axis to all but
    the platform matrix."""
    f64, b = jnp.float64, jnp.bool_
    sk, sd = lead + (s, k), lead + (d,)
    return _shapes(sharding, (sk, f64), (sk, b), (sk, f64), (sk, b),
                   (sk, b), (lead + (s,), f64), *[(sd, f64)] * 6) + \
        _shapes(pmat_sharding or sharding,
                ((p, len(workload_engine.PLATFORM_FIELDS)), f64))


def _golden_fold_shape(name: str) -> tuple[int, int, int, int]:
    spec = SymbolicSweepSpec.load(
        os.path.join(ROOT, "specs", f"{name}.json")).resolve()
    return workload_engine.fold_shape(
        len(spec.scenarios), max(len(s.streams) for s in spec.scenarios),
        len(spec.designs), len(spec.platforms))


@pytest.mark.parametrize("anchor_peri", [True, False])
def test_ppa_kernel_compiles(one_chip, chip_compile, anchor_peri):
    n, m, c = 4, 3, len(scenarios.MEGA_CAPACITIES_MB)
    o = engine.N_ORGS
    f64, i64 = jnp.float64, jnp.int64
    args = _shapes(one_chip, ((n, m, 7), f64), ((n, m, 8), f64),
                   ((m,), jnp.bool_), ((n, 4), f64), ((n, 7), f64),
                   ((c,), i64), *[((o,), i64)] * 4)
    compiled = engine._ppa_kernel.lower(
        *args, anchor_peri=anchor_peri).compile()
    out = compiled.out_info
    assert out["read_latency_s"].shape == (n, m, c, o)
    assert out["read_latency_s"].dtype == jnp.float64
    assert out["leakage_w"].shape == (n, m, c)


FOLD_SHAPES = [
    pytest.param(MEGA_CHUNK, id="mega-chunk"),
    pytest.param("isocap", id="golden-isocap"),
]


def _fold_shape(shape) -> tuple[int, int, int, int]:
    return _golden_fold_shape(shape) if isinstance(shape, str) else shape


@pytest.mark.parametrize("shape", FOLD_SHAPES)
def test_fold_kernel_compiles(one_chip, chip_compile, shape):
    s, k, d, p = _fold_shape(shape)
    compiled = jax.jit(workload_engine._fold).lower(
        *_fold_args(one_chip, s, k, d, p)).compile()
    assert compiled.out_info["runtime_s"].shape == (p, s, d)
    assert compiled.out_info["dram_tx"].dtype == jnp.float64


@pytest.mark.parametrize("shape", FOLD_SHAPES)
def test_packed_fold_kernel_compiles(one_chip, chip_compile, shape):
    s, k, d, p = _fold_shape(shape)
    compiled = workload_engine._fold_packed.lower(
        *_fold_args(one_chip, s, k, d, p)).compile()
    assert compiled.out_info.shape == (2 * s + 3 * s * d + 5 * p * s * d,)
    assert compiled.out_info.dtype == jnp.float64
    if shape == MEGA_CHUNK:
        assert compiled.out_info.shape == (PACKED_N,)


def test_sharded_fold_has_no_collectives(topo, chip_compile):
    from jax.sharding import Mesh

    from repro.distributed.sharding import SWEEP_AXIS

    mesh = Mesh(np.array(topo.devices[:4]), (SWEEP_AXIS,))
    args = _fold_args(NamedSharding(mesh, P(SWEEP_AXIS)), *MEGA_CHUNK,
                      lead=(4,), pmat_sharding=NamedSharding(mesh, P()))
    compiled = workload_engine._sharded_fold(mesh).lower(*args).compile()
    assert compiled.out_info.shape == (4, PACKED_N)
    hlo = compiled.as_text()
    for collective in ("all-reduce", "all-gather", "all-to-all",
                       "collective-permute", "reduce-scatter"):
        assert collective not in hlo, collective


def test_flash_attention_kernel_compiles(one_chip, no_compile_cache):
    from repro.kernels import flash_attention as fa
    from repro.kernels import ops

    # ops.attention routes q-lengths >= FLASH_THRESHOLD to the kernel
    b, s, h, hd = 1, ops.FLASH_THRESHOLD, 8, 128
    q, k, v = _shapes(one_chip, *[((b, s, h, hd), jnp.bfloat16)] * 3)
    compiled = jax.jit(fa.flash_attention).lower(q, k, v).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_wkv6_kernel_compiles(one_chip, no_compile_cache):
    from repro.configs import rwkv6_3b
    from repro.kernels import rwkv6

    cfg = rwkv6_3b.config()
    b, s, h, hd = 1, 2048, cfg.n_heads, cfg.head_dim
    r, k, v, w = _shapes(one_chip, *[((b, s, h, hd), jnp.float32)] * 4)
    (u,) = _shapes(one_chip, ((h, hd), jnp.float32))
    compiled = jax.jit(rwkv6.wkv6).lower(r, k, v, w, u).compile()
    assert "tpu_custom_call" in compiled.as_text()
