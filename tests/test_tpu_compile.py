"""The store's device kernels compile for a TPU v5e chip at the chip
smoke's sizes.

Nothing runs: each kernel is lowered and compiled for one chip of a
described, not attached, ``v5e:2x2`` topology, which surfaces the TPU
compiler's refusals without a chip.  Shapes are those of
``chip_smoke.py`` at R-MAT scale 20: 2^20 vertices, 2^25 undirected edge
records.  Each of these kernels is a data-dependent gather at heart,
which Mosaic does not lower, so each is jnp and must compile as plain
XLA: no Pallas custom call may appear.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import merge, presence, segment_reduce

V = 1 << 20          # scale-20 vertex space
E = 1 << 25          # undirected edge records (2 x edge factor 16 x V)
QUERIES = 4096       # point-read batch
RUNS = 8             # visible runs stacked for the presence test
FILTER_WORDS = 1 << 19  # 16 bits/key x 2^20 keys / 32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def _segsum(dst, seg, wt, x):
    return segment_reduce.gather_segsum(dst, seg, wt, x, n_out=V)


def _segmin(dst, seg, wt, x):
    return segment_reduce.gather_segmin(dst, seg, wt, x, n_out=V)


def _merge(a, b):
    return merge.merge_streams(a, b)


_I32, _F32, _U32, _BOOL = jnp.int32, jnp.float32, jnp.uint32, jnp.bool_
_SPINE = (_I32, _I32, _I32, _I32, _BOOL, _F32)  # src dst ts rid marker prop

# name -> (function, argument shapes)
CASES = {
    "gather_segsum": (_segsum, [((E,), _I32), ((E,), _I32), ((E,), _F32),
                                ((V,), _F32)]),
    "gather_segmin": (_segmin, [((E,), _I32), ((E,), _I32), ((E,), _F32),
                                ((V,), _F32)]),
    "merge_streams": (_merge, [tuple(((E // 2,), t) for t in _SPINE),
                               tuple(((E // 2,), t) for t in _SPINE)]),
    "presence_matrix": (presence.presence_matrix,
                        [((RUNS, FILTER_WORDS), _U32), ((RUNS,), _U32),
                         ((QUERIES,), _I32)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_compiles_for_v5e(name, one_chip, no_persistent_cache):
    fn, shapes = CASES[name]

    def spec(s):
        if isinstance(s[1], tuple):  # a tuple of (shape, dtype) leaves
            return tuple(spec(x) for x in s)
        return jax.ShapeDtypeStruct(s[0], s[1], sharding=one_chip)

    compiled = jax.jit(fn).lower(*[spec(s) for s in shapes]).compile()
    # A Pallas kernel would reach the chip as a Mosaic custom call.
    assert "tpu_custom_call" not in compiled.as_text()
