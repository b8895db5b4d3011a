"""The reduction from a profiler trace to per-layer numbers, on two small
traces recorded on a TPU v5e (a train step and the serving loop) and on
made-up ones."""
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import trace as tr

DATA = pathlib.Path(__file__).parent / "data"


def _recorded(name):
    return json.loads((DATA / f"trace_{name}.json").read_text())


def test_union_merges_overlaps_and_clips_to_the_window():
    spans = [(0, 10), (5, 15), (20, 30), (28, 29), (40, 50)]
    assert tr._union(spans, 0, 100) == 15 + 10 + 10
    assert tr._union(spans, 8, 45) == 7 + 10 + 5


def test_gaps_are_the_window_minus_the_busy_union():
    spans = [(2, 4), (3, 6), (10, 12)]
    assert tr._gaps(spans, 0, 15) == [(0, 2), (6, 10), (12, 15)]


def _synthetic():
    ms = 1_000_000
    ops = [[0, 4 * ms, "dot.1", "jit_step", "jit(step)/agg/gram/dot_general"],
           [4 * ms, 2 * ms, "sort.2", "jit_step",
            "jit(step)/agg/select/agg/coordinate/sort"],
           [6 * ms, 1 * ms, "fusion.3", "jit_step",
            "jit(step)/transpose(jvp(loss))/aggregate_like/mul"],
           [9 * ms, 1 * ms, "fusion.4", "jit_step",
            "jit(step)/kernel/fused/pallas_call"]]
    return {"window": [0, 12 * ms],
            "devices": {"/device:TPU:0": ops},
            "modules": {"/device:TPU:0": [[0, 10 * ms, "jit_step"]]},
            "host": [[0, 12 * ms, "bench/window"],
                     [6.5 * ms, 4 * ms, "bench/wait"]]}


def test_reduce_made_up_trace():
    red = tr.reduce(_synthetic())
    assert red["window_s"] == pytest.approx(0.012)
    assert red["busy_s"] == pytest.approx(0.008)
    assert red["idle_share"] == pytest.approx(4 / 12)
    # whole path components only: "aggregate_like" is not "agg"
    assert tr.scope_seconds(red, "agg") == pytest.approx(0.006)
    assert tr.scope_seconds(red, "agg", "kernel") == pytest.approx(0.007)
    assert red["module_calls"] == {"jit_step": [0.01]}
    # the gap at 7-9 ms lies in bench/wait, the one at 10-12 ms in the
    # window span alone
    gaps = {name: secs for name, secs, _n, _l in red["gaps"]}
    assert gaps == pytest.approx({"bench/wait": 0.002,
                                  "bench/window": 0.002})
    bd = tr.breakdown(red)
    assert bd["device_ops"][0] == ["agg/gram/dot", pytest.approx(0.004)]
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def _busy_by_numpy(ops, lo, hi):
    """Busy time by a 1-ns occupancy grid, independent of ``_union``."""
    base = int(lo)
    grid = np.zeros(int(hi - lo) + 1, bool)
    for s, d, *_ in ops:
        a, b = max(int(s), base), min(int(s + d), int(hi))
        if b > a:
            grid[a - base:b - base] = True
    return grid[:-1].sum()


@pytest.mark.parametrize("name", ["train", "serve"])
def test_reduce_recorded_trace(name):
    t = _recorded(name)
    red = tr.reduce(t)
    lo, hi = t["window"]
    assert 0.0 <= red["idle_share"] <= 1.0
    (ops,) = t["devices"].values()
    assert red["busy_s"] * 1e9 == pytest.approx(
        _busy_by_numpy(ops, lo, hi), abs=2.0)
    idle = sum(s for _n, s, _c, _l in red["gaps"])
    assert idle + red["busy_s"] == pytest.approx(red["window_s"], rel=1e-6)


def test_recorded_serving_loop_idles_in_admission():
    red = tr.reduce(_recorded("serve"))
    assert red["gaps"][0][0] == "bench/admit"
    assert red["idle_share"] > 0.5
    assert "jit_scan" in red["module_calls"]


def test_hlo_scopes_names_each_instruction():
    def step(x):
        with jax.named_scope("agg"):
            with jax.named_scope("gram"):
                g = x @ x.T
        return jnp.sort(g, axis=0).sum()

    text = jax.jit(step).lower(jnp.ones((8, 8))).compile().as_text()
    scopes = tr.hlo_scopes(text)
    (module,) = scopes
    assert module.startswith("jit_step")
    assert any("agg/gram" in path for path in scopes[module].values())
