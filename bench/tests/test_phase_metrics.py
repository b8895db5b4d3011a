"""The per-phase readers of the train step (``coord_ms.train``,
``select_ms.train``, ``grad_ms.train``) on made-up traces: one of a
program whose phases each have a scope of their own, one of a program
whose coordinate phase nests inside ``agg/select`` and whose gradients
carry no scope, and one with nothing to read."""
import pytest
from test_metrics import _EMPTY, MS, _train_ctx, _train_trace

from harness import common

PHASES = ("coord_ms.train", "select_ms.train", "grad_ms.train")


def _read(name, ctx):
    return common.module("metrics", name).read(ctx)


def _scoped_trace():
    """Two steps of a program with one scope per phase."""
    ops = []
    for step in range(2):
        t = step * 3000 * MS
        ops += [
            [t, 30 * MS, "fusion.1", "jit_step",
             "jit(step)/train/grads/vmap(jvp())/dot_general"],
            [t + 30 * MS, 60 * MS, "fusion.2", "jit_step",
             "jit(step)/train/grads/vmap(transpose(jvp()))/dot_general"],
            [t + 90 * MS, 5 * MS, "fusion.3", "jit_step",
             "jit(step)/train/inject/max"],
            [t + 95 * MS, 12 * MS, "dot.4", "jit_step",
             "jit(step)/agg/gram/dot_general"],
            [t + 107 * MS, 80 * MS, "while.5", "jit_step",
             "jit(step)/agg/select/while"],
            [t + 187 * MS, 2320 * MS, "fusion.6", "jit_step",
             "jit(step)/agg/coordinate/gather"],
            [t + 2507 * MS, 8 * MS, "fusion.7", "jit_step",
             "jit(step)/train/optimizer/transpose(jvp())/dot_general"],
            [t + 2515 * MS, 4 * MS, "fusion.8", "jit_step",
             "jit(step)/train/diagnostics/reduce_sum"]]
    trace = {"window": [0, 6000 * MS], "devices": {"/device:TPU:0": ops},
             "modules": {"/device:TPU:0": [[0, 2519 * MS, "jit_step"]]},
             "host": [[0, 6000 * MS, "bench/window"]]}
    ctx = _train_ctx(trace)
    ctx["trace"]["steps"] = 2
    return ctx


def test_each_phase_reads_its_own_scope():
    c = _scoped_trace()
    assert _read("coord_ms.train", c) == pytest.approx(2320.0)
    assert _read("select_ms.train", c) == pytest.approx(80.0)
    # a gradient outside ``train/grads`` (a clean auxiliary batch under
    # ``train/optimizer``) is not the workers'
    assert _read("grad_ms.train", c) == pytest.approx(90.0)


def test_phases_and_the_gram_sum_to_the_aggregation():
    from harness.trace import scope_seconds

    c = _scoped_trace()
    gram_ms = 1000.0 * scope_seconds(c["trace"], "agg/gram") / 2
    assert (_read("coord_ms.train", c) + _read("select_ms.train", c)
            + gram_ms) == pytest.approx(_read("agg_ms.train", c))


def test_nested_phases_read_apart():
    """A program whose rule runs wholly inside ``agg/select`` and whose
    gradients have no scope: selection leaves the nested coordinate phase
    out, and the gradients are read by their transforms."""
    c = _train_ctx(_train_trace())
    assert _read("coord_ms.train", c) == pytest.approx(2320.0)
    assert _read("select_ms.train", c) == pytest.approx(80.0)
    assert _read("grad_ms.train", c) == pytest.approx(25.0)


@pytest.mark.parametrize("name", PHASES)
def test_phase_reads_nothing_without_its_scope(name):
    assert _read(name, _train_ctx(_EMPTY)) is None
