"""The fold's packed output: one float64 buffer per chunk, unpacked on the
host into the same ten outputs the dict-returning fold gives, bit for bit.

The plain kernel (``_fold_packed``) and the ``shard_map``'d one (on a
one-device sweep mesh) are both held to the dict of ``_fold`` jitted as it
stands, at the mega-sweep chunk shape and at a ragged one; the plain
program must still lower under ``_fold``'s name.
"""

import re

import jax
import numpy as np
import pytest

from repro.core import workload_engine
from repro.distributed.sharding import sweep_mesh

SHAPES = [
    pytest.param((8, 1024, 32, 2), id="mega-chunk"),
    pytest.param((3, 8, 5, 3), id="ragged"),
]


def fold_args(s, k, d, p, seed=0):
    """Seeded fold inputs of shape (s, k, d, p): real and padded streams,
    finite and infinite reuse distances, reads and writes."""
    rng = np.random.default_rng(seed)
    mask = rng.random((s, k)) < 0.7
    mask[:, 0] = True
    bytes_total = np.where(mask, rng.uniform(1e3, 1e9, (s, k)), 0.0)
    reuse = np.where(rng.random((s, k)) < 0.2, np.inf,
                     rng.uniform(1e3, 1e8, (s, k)))
    reuse[~mask] = np.inf
    designs = [rng.uniform(1e-10, 1e-8, d), rng.uniform(1e-10, 1e-8, d),
               rng.uniform(1e-12, 1e-10, d), rng.uniform(1e-12, 1e-10, d),
               rng.uniform(1e-3, 1.0, d),
               rng.choice([1, 2, 4, 8, 16], d) * 2.0**20]
    pmat = rng.uniform(1.0, 1e13, (p, len(workload_engine.PLATFORM_FIELDS)))
    return (bytes_total, rng.random((s, k)) < 0.3, reuse,
            rng.random((s, k)) < 0.8, mask, rng.uniform(1e6, 1e10, s),
            *designs, pmat)


def dict_fold(args):
    with jax.enable_x64(True):
        return {k: np.asarray(v)
                for k, v in jax.jit(workload_engine._fold)(*args).items()}


def assert_bit_equal(got: dict, want: dict):
    assert list(got) == [k for k, _ in workload_engine._FOLD_LAYOUT]
    assert set(got) == set(want)
    for k, v in want.items():
        assert got[k].dtype == np.float64, k
        assert got[k].shape == v.shape, k
        np.testing.assert_array_equal(got[k], v, err_msg=k)


@pytest.mark.parametrize("shape", SHAPES)
def test_packed_fold_unpacks_to_the_dict_fold(shape):
    s, k, d, p = shape
    args = fold_args(s, k, d, p)
    with jax.enable_x64(True):
        buf = np.asarray(workload_engine._fold_packed(*args))
    assert buf.shape == (2 * s + 3 * s * d + 5 * p * s * d,)
    got = workload_engine._unpack(buf, p, s, d)
    assert_bit_equal(got, dict_fold(args))
    # views of the one copy, not copies of it
    assert all(np.shares_memory(v, buf) for v in got.values())


@pytest.mark.parametrize("shape", SHAPES)
def test_sharded_packed_fold_matches_on_one_device(shape):
    s, k, d, p = shape
    args = fold_args(s, k, d, p, seed=1)
    with jax.enable_x64(True):
        out = workload_engine._sharded_fold(sweep_mesh(1))(
            *[a[None] for a in args[:-1]], args[-1])
        buf = np.asarray(out)
    assert buf.shape == (1, 2 * s + 3 * s * d + 5 * p * s * d)
    assert_bit_equal(workload_engine._unpack(buf[0], p, s, d),
                     dict_fold(args))


def test_unpack_refuses_a_buffer_of_another_layout():
    with pytest.raises(ValueError, match="layout"):
        workload_engine._unpack(np.zeros(3344 + 1), 2, 8, 32)


def test_packed_fold_keeps_the_profilers_name():
    with jax.enable_x64(True):
        text = workload_engine._fold_packed.lower(
            *fold_args(8, 16, 32, 2)).as_text()
    assert re.search(r"module @(\S+)", text).group(1) == "jit__fold"
