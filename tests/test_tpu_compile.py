"""The aggregation kernels compile for a TPU v5e at the train cells' shapes.

Nothing here runs on a chip: each test lowers one kernel with
``interpret=False`` for a described (not attached) ``v5e:2x2`` topology
and compiles it with the TPU compiler, which refuses what the chip would
refuse (unaligned tiles, too much VMEM).  The stacks are bf16 rows of
d = 2**20 coordinates; the committees are the smallest Bulyan quorum
(n = 7, f = 1) and the paper's n = 39, f = 9.

The robust train step compiles for the same chip too, and every fusion
that computes an aggregation op carries an ``agg`` scope, which is where
the device trace's readers look for the aggregation's time.

The topology is described inside a module-scoped fixture, never at
import: only one process may hold the TPU library, and every test
worker imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.agg import AggSpec
from repro.configs import get_reduced
from repro.dist.train import make_train_step
from repro.kernels import (bulyan_select, coord_stats, fused_aggregate,
                           pairwise_gram)
from repro.models import init_model
from repro.optim import get_optimizer

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


def _fusions(hlo: str):
    """``(op_name of the fusion, op_names of the ops it computes)`` for
    every fusion of a compiled module's text."""
    bodies, body = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head:
            body = bodies.setdefault(head.group(1), [])
        elif body is not None:
            body.append(line)
    op_name = re.compile(r'op_name="([^"]*)"')
    for lines in bodies.values():
        for line in lines:
            if " fusion(" not in line:
                continue
            called = re.search(r"calls=%?([\w.\-]+)", line).group(1)
            own = op_name.search(line)
            yield (own.group(1) if own else "",
                   [m.group(1) for x in bodies.get(called, [])
                    for m in [op_name.search(x)] if m])


@pytest.mark.parametrize("gar", ["bulyan-krum", "trimmed_mean"])
def test_aggregation_fusions_keep_the_agg_scope(one_chip, gar):
    """XLA names a fusion after its root.  Were the aggregate fused into
    the optimizer's or the diagnostics' fusion, the rule's ops would run
    under ``train/...`` and ``agg_ms.train`` would leave them out."""
    cfg = get_reduced("qwen1_5_4b")
    opt = get_optimizer("adamw", 3e-4, weight_decay=0.01)
    spec = AggSpec(f=1, gar=gar, attack="omniscient_linf",
                   attack_kwargs=(("margin", 3.0),))
    step = make_train_step(cfg, spec, opt)
    params = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg))
    state = jax.eval_shape(opt.init, params)
    batch = {k: jax.ShapeDtypeStruct((7, 1, 16), jnp.int32)
             for k in ("tokens", "labels")}
    on_chip = lambda t: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        t)
    hlo = jax.jit(step).lower(on_chip(params), on_chip(state),
                              on_chip(batch)).compile().as_text()
    agg = [(own, inner) for own, inner in _fusions(hlo)
           if any("/agg/" in x for x in inner)]
    assert agg
    wrong = [own for own, _ in agg if "/agg/" not in own]
    assert not wrong, wrong[:5]
