"""Each per-layer metric's reader, found by its name in ``BENCHMARK.json``:
it reads a number where its cell gives one, a share stays within 100%,
and it returns nothing where there is nothing to read."""
import pytest

from harness import common
from harness import trace as tr
from harness.peaks import peaks

TRAIN = "qwen1.5-4b-1L.train.short256"
SERVE = "qwen1.5-4b-1L.serve.chat8"
MS = 1_000_000


def _train_trace():
    """One 2.6-s step as the chip ran it: the coordinate phase's gather
    most of it, the selection loop, the Gram, the rest outside ``agg/``."""
    ops = [[0, 12 * MS, "dot.1", "jit_step", "jit(step)/agg/gram/dot"],
           [12 * MS, 80 * MS, "while.2", "jit_step", "jit(step)/agg/select/while"],
           [92 * MS, 2320 * MS, "fusion.3", "jit_step",
            "jit(step)/agg/select/agg/coordinate/gather"],
           [2412 * MS, 25 * MS, "fusion.4", "jit_step",
            "jit(step)/transpose(jvp(loss))/dot_general"]]
    return {"window": [0, 2440 * MS], "devices": {"/device:TPU:0": ops},
            "modules": {"/device:TPU:0": [[0, 2437 * MS, "jit_step"]]},
            "host": [[0, 2440 * MS, "bench/window"]]}


def _serve_trace():
    ops = [[0, 24 * MS, "fusion.1", "jit_serve_step", ""],
           [300 * MS, 24 * MS, "fusion.2", "jit_serve_step", ""]]
    return {"window": [0, 400 * MS], "devices": {"/device:TPU:0": ops},
            "modules": {"/device:TPU:0": [[0, 24 * MS, "jit_serve_step"],
                                          [300 * MS, 24 * MS,
                                           "jit_serve_step"]]},
            "host": [[0, 400 * MS, "bench/window"],
                     [30 * MS, 260 * MS, "bench/admit"]]}


def _ctx(cell, red, e2e):
    w = common.workload(cell)
    return {"cell": cell, "cfg": common.config(w["config"]),
            "mix": common.traffic(w["traffic"]), "trace": red,
            "peaks": peaks("TPU v5 lite"), "chips": w["chips"], "e2e": e2e}


def _train_ctx(trace):
    red = tr.reduce(trace)
    red["steps"] = 1
    return _ctx(TRAIN, red, {"train_tokens_per_s": (681.9, "tokens/s")})


def _serve_ctx(trace, harness_numbers):
    red = tr.reduce(trace)
    red.update(harness_numbers)
    return _ctx(SERVE, red, {"serve_tokens_per_s": (136.5, "tokens/s")})


_SERVE_NUMBERS = {"admit_ms": 270.0, "model_flops": 5.6e13,
                  "window_s_e2e": 30.0, "ttft_p95_s": 15.0,
                  "itl_p95_s": 0.28}
_EMPTY = {"window": [0, MS], "devices": {}, "modules": {}, "host": []}


def _metrics(cell):
    return [m["name"] for m in common.metrics_of(cell, "per_layer")]


@pytest.mark.parametrize("cell,ctx", [
    (TRAIN, lambda: _train_ctx(_train_trace())),
    (SERVE, lambda: _serve_ctx(_serve_trace(), _SERVE_NUMBERS))])
def test_every_metric_reads_its_cell(cell, ctx):
    c = ctx()
    for name in _metrics(cell):
        value = common.module("metrics", name).read(c)
        assert value is not None and value > 0, name
        if name.endswith("_roofline") or "mfu" in name or "share" in name:
            assert value <= 100.0, name


def test_train_metrics_from_the_step():
    c = _train_ctx(_train_trace())
    read = lambda name: common.module("metrics", name).read(c)  # noqa: E731
    assert read("agg_ms.train") == pytest.approx(2412.0)
    assert read("idle_share.train") == pytest.approx(100 * 3 / 2440)
    # least time 6.9 ms: 8 f32 copies of 176,552,960 coordinates at 819 GB/s
    assert read("agg_roofline") == pytest.approx(
        100 * 8 * 4 * 176_552_960 / 819e9 / 2.412)
    assert read("mfu.train") == pytest.approx(0.267, rel=0.01)


def test_serve_decode_is_the_mean_call():
    c = _serve_ctx(_serve_trace(), _SERVE_NUMBERS)
    assert common.module("metrics", "decode_ms.serve").read(c) == \
        pytest.approx(24.0)


@pytest.mark.parametrize("cell,ctx", [
    (TRAIN, lambda: _train_ctx(_EMPTY)),
    (SERVE, lambda: _serve_ctx(_EMPTY, {}))])
def test_nothing_to_read_gives_nothing(cell, ctx):
    c = ctx()
    for name in _metrics(cell):
        if name == "mfu.train":         # reads the window's rate, always there
            continue
        assert common.module("metrics", name).read(c) is None, name
