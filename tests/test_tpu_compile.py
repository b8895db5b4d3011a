"""The aggregation kernels compile for a TPU v5e at the train cells' shapes.

Nothing here runs on a chip: each test lowers one kernel with
``interpret=False`` for a described (not attached) ``v5e:2x2`` topology
and compiles it with the TPU compiler, which refuses what the chip would
refuse (unaligned tiles, too much VMEM).  The stacks are bf16 rows of
d = 2**20 coordinates; the committees are the smallest Bulyan quorum
(n = 7, f = 1) and the paper's n = 39, f = 9.

The robust train step compiles for the same chip too, and every fusion
that computes an aggregation op carries an ``agg`` scope, which is where
the device trace's readers look for the aggregation's time.  Its
Byzantine injection reads each honest leaf once and writes the ``f``
rows into the gradient stack in place.

The topology is described inside a module-scoped fixture, never at
import: only one process may hold the TPU library, and every test
worker imports this file.
"""
import dataclasses
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


def _compiled_step(one_chip, cfg, spec) -> str:
    """The compiled text of the robust train step for one described
    chip: 7 workers, one 16-token sequence each, AdamW."""
    opt = get_optimizer("adamw", 3e-4, weight_decay=0.01)
    step = make_train_step(cfg, spec, opt)
    params = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg))
    state = jax.eval_shape(opt.init, params)
    batch = {k: jax.ShapeDtypeStruct((7, 1, 16), jnp.int32)
             for k in ("tokens", "labels")}
    on_chip = lambda t: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        t)
    return jax.jit(step).lower(on_chip(params), on_chip(state),
                               on_chip(batch)).compile().as_text()


@pytest.mark.parametrize("gar", ["bulyan-krum", "trimmed_mean"])
def test_aggregation_fusions_keep_the_agg_scope(one_chip, gar):
    """XLA names a fusion after its root.  Were the aggregate fused into
    the optimizer's or the diagnostics' fusion, the rule's ops would run
    under ``train/...`` and ``agg_ms.train`` would leave them out."""
    spec = AggSpec(f=1, gar=gar, attack="omniscient_linf",
                   attack_kwargs=(("margin", 3.0),))
    hlo = _compiled_step(one_chip, get_reduced("qwen1_5_4b"), spec)
    agg = [(own, inner) for own, inner in _fusions(hlo)
           if any("/agg/" in x for x in inner)]
    assert agg
    wrong = [own for own, _ in agg if "/agg/" not in own]
    assert not wrong, wrong[:5]


_INSTR = re.compile(r"\s*(?:ROOT )?%([\w.\-]+) = (.+?) ([\w\-]+)\((.*?)\)"
                    r"(?:, |$)")


def _instructions(hlo: str) -> dict:
    """``{computation: {name: (shape, opcode, operands, line)}}`` of a
    compiled module's text."""
    comps, body = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head:
            body = comps.setdefault(head.group(1), {})
            continue
        m = _INSTR.match(line) if body is not None else None
        if m:
            body[m.group(1)] = (m.group(2), m.group(3),
                                re.findall(r"%([\w.\-]+)", m.group(4)), line)
    return comps


def _lead(shape: str):
    m = re.match(r"\w+\[(\d+)", shape)
    return int(m.group(1)) if m else None


def _written_only(body: dict, name: str) -> bool:
    """Whether a fused computation's value ``name`` is only the buffer a
    ``dynamic-update-slice`` writes into (its operand 0), directly or
    through bitcasts: the stack updated in place, not read."""
    users = [(k, op, args) for k, (_, op, args, _) in body.items()
             if name in args]
    return bool(users) and all(
        (op == "dynamic-update-slice" and args.index(name) == 0
         and args.count(name) == 1)
        or (op == "bitcast" and _written_only(body, k))
        for k, op, args in users)


def _source(body: dict, name: str) -> str:
    """The buffer a value of a computation is a bitcast of."""
    while name in body and body[name][1] == "bitcast":
        name = body[name][2][0]
    return name


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_injection_reads_each_honest_leaf_once(one_chip, dtype):
    """The omniscient L-inf adversary needs one read of the honest rows
    (their means and the global ``delta_bar``) and one write of the
    Byzantine row into the stack ``train/grads`` produced.  No op under
    ``train/inject`` builds an ``(n, ...)`` stack anew (a ``concatenate``,
    or the ``pad``s and ``copy``s XLA makes of one), no in-place update
    starts from a copy of the stack, and no fusion under it reads a
    worker stack that another such fusion reads: the mean row is written
    once and read back, the honest rows are not."""
    n = 7
    cfg = dataclasses.replace(get_reduced("qwen1_5_4b"), param_dtype=dtype)
    spec = AggSpec(f=1, gar="bulyan-krum", attack="omniscient_linf",
                   attack_kwargs=(("margin", 3.0),))
    comps = _instructions(_compiled_step(one_chip, cfg, spec))
    calls = lambda line: re.search(r"calls=%([\w.\-]+)", line).group(1)
    fused = {calls(line) for body in comps.values()
             for _, op, _, line in body.values() if op == "fusion"}

    def inside(comp):
        """The instructions of a fused computation and of those it calls."""
        for shape, op, args, line in comps[comp].values():
            yield shape, op, line
            if op == "fusion":
                yield from inside(calls(line))

    readers, in_place, rebuilt = {}, 0, []
    for cname, body in comps.items():
        if cname in fused:
            continue
        for name, (shape, op, args, line) in body.items():
            if "/train/inject/" not in line:
                continue
            ops = ([(shape, op, line)] if op != "fusion"
                   else list(inside(calls(line))))
            rebuilt += [l.strip()[:160] for sh, o, l in ops
                        if o in ("concatenate", "pad", "copy")
                        and _lead(sh) == n]
            if op != "fusion":
                continue
            called = comps[calls(line)]
            params = {int(re.search(r"parameter\((\d+)\)", v[3]).group(1)): k
                      for k, v in called.items() if v[1] == "parameter"}
            for i, arg in enumerate(args):
                src = _source(body, arg)
                if src not in body or _lead(body[src][0]) != n:
                    continue
                if _written_only(called, params[i]):
                    in_place += 1
                    assert body[src][1] != "copy", line.strip()[:160]
                else:
                    readers.setdefault(src, set()).add(name)
    assert not rebuilt, rebuilt[:5]
    assert in_place
    assert readers
    twice = {k: sorted(v) for k, v in readers.items() if len(v) > 1}
    assert not twice, twice
