"""The aggregation kernels compile for a TPU v5e at the train cells' shapes.

Nothing here runs on a chip: each test lowers one kernel with
``interpret=False`` for a described (not attached) ``v5e:2x2`` topology
and compiles it with the TPU compiler, which refuses what the chip would
refuse (unaligned tiles, too much VMEM).  The stacks are bf16 rows of
d = 2**20 coordinates; the committees are the smallest Bulyan quorum
(n = 7, f = 1) and the paper's n = 39, f = 9.

The topology is described inside a module-scoped fixture, never at
import: only one process may hold the TPU library, and every test
worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import (bulyan_select, coord_stats, fused_aggregate,
                           pairwise_gram)

D = 2 ** 20
#: one prime past 10**6: no block width divides it
RAGGED_D = 1_000_003
#: (n, f) committees: the smallest Bulyan quorum and the paper's Fig 4-6
COMMITTEES = [(7, 1), (39, 9)]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _stack(rows, d, sharding):
    return jax.ShapeDtypeStruct((rows, d), jnp.bfloat16, sharding=sharding)


def _compiled_text(kernel, *args, **static) -> str:
    return kernel.lower(*args, interpret=False, **static).compile().as_text()


@pytest.mark.parametrize("n,f", COMMITTEES)
def test_pairwise_gram_compiles(one_chip, n, f):
    assert "tpu_custom_call" in _compiled_text(
        pairwise_gram, _stack(n, D, one_chip))


@pytest.mark.parametrize("n,f", COMMITTEES)
def test_coord_stats_compiles(one_chip, n, f):
    assert "tpu_custom_call" in _compiled_text(
        coord_stats, _stack(n, D, one_chip), f)


@pytest.mark.parametrize("n,f", COMMITTEES)
def test_bulyan_select_compiles(one_chip, n, f):
    theta = n - 2 * f
    assert "tpu_custom_call" in _compiled_text(
        bulyan_select, _stack(theta, D, one_chip), f)


@pytest.mark.parametrize("d", [D, RAGGED_D])
@pytest.mark.parametrize("n,f", COMMITTEES)
def test_fused_aggregate_compiles(one_chip, n, f, d):
    assert "tpu_custom_call" in _compiled_text(
        fused_aggregate, _stack(n, d, one_chip), f, mode="bulyan-krum")
