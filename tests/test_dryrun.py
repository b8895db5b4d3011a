"""Dry-run smoke: one reduced (arch x shape) lower+compile in a 512-device
subprocess, validating the artifact schema the roofline analysis consumes.
The full-size matrix is produced by repro.launch.sweep (see EXPERIMENTS.md).
"""
import json
import os
import pathlib
import subprocess
import sys
import tempfile

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, timeout=900):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    return subprocess.run([sys.executable, "-m", "repro.launch.dryrun",
                           *args], capture_output=True, text=True,
                          timeout=timeout, env=env)


@pytest.mark.slow
def test_reduced_dryrun_train_artifact():
    with tempfile.TemporaryDirectory() as td:
        out = os.path.join(td, "a.json")
        r = _run(["--arch", "mamba2-130m", "--shape", "train_4k",
                  "--reduced", "--out", out])
        assert r.returncode == 0, r.stderr[-3000:]
        rec = json.load(open(out))
    assert rec["mesh"] == "16x16"
    roof = rec["roofline"]
    for k in ("compute_s", "memory_s", "collective_s", "dominant",
              "useful_flops_ratio"):
        assert k in roof
    assert roof["compute_s"] > 0
    assert sum(v["count"] for v in rec["collectives"].values()) > 0


@pytest.mark.slow
def test_reduced_dryrun_multipod_decode():
    with tempfile.TemporaryDirectory() as td:
        out = os.path.join(td, "b.json")
        r = _run(["--arch", "gemma3-1b", "--shape", "decode_32k",
                  "--reduced", "--multi-pod", "--out", out])
        assert r.returncode == 0, r.stderr[-3000:]
        rec = json.load(open(out))
    assert rec["mesh"] == "2x16x16"
    assert rec["multi_pod"] is True


@pytest.mark.slow
def test_reduced_dryrun_robust_ensemble_decode():
    """--serve-gar: the robust ensemble decode step lowers + compiles on
    the production mesh with the replica axis on ``data``."""
    with tempfile.TemporaryDirectory() as td:
        out = os.path.join(td, "c.json")
        r = _run(["--arch", "gemma3-1b", "--shape", "decode_32k",
                  "--reduced", "--serve-gar", "bulyan-krum",
                  "--serve-f", "1", "--serve-replicas", "7",
                  "--out", out])
        assert r.returncode == 0, r.stderr[-3000:]
        rec = json.load(open(out))
    assert rec["serve_gar"] == "bulyan-krum"
    assert rec["serve_replicas"] == 7
    assert rec["hlo_lines"] > 0


@pytest.mark.slow
def test_reduced_dryrun_async_stale_train():
    """--async-tau + --gar stale-*: the asynchronous bounded-staleness
    train step lowers + compiles on the production mesh with the
    GradientBus-carrying AggState initialized via eval_shape (nothing
    materialized), including the delay-exploiting in-graph attack."""
    with tempfile.TemporaryDirectory() as td:
        out = os.path.join(td, "d.json")
        r = _run(["--arch", "mamba2-130m", "--shape", "train_4k",
                  "--reduced", "--async-tau", "3", "--async-schedule",
                  "fixed", "--gar", "stale-bulyan-krum", "--attack",
                  "stale_replay", "--out", out])
        assert r.returncode == 0, r.stderr[-3000:]
        rec = json.load(open(out))
    assert rec["async_tau"] == 3
    assert rec["gar"] == "stale-bulyan-krum"
    assert rec["roofline"]["compute_s"] > 0
    assert rec["hlo_lines"] > 0


def test_long_500k_skip_rules():
    from repro.configs import shape_applicable
    assert shape_applicable("mamba2-130m", "long_500k")
    assert shape_applicable("mixtral-8x22b", "long_500k")
    assert shape_applicable("jamba-1.5-large-398b", "long_500k")
    assert not shape_applicable("gemma-2b", "long_500k")
    assert not shape_applicable("whisper-medium", "long_500k")
    assert not shape_applicable("llama-3.2-vision-11b", "long_500k")
    assert shape_applicable("gemma-2b", "train_4k")


def test_peaks_by_device_kind():
    """The roofline peaks come from one table keyed by ``device_kind``; a
    chip that is not in it raises instead of borrowing v5e numbers."""
    from repro.launch.device import PRODUCTION_KIND, peaks
    assert peaks(PRODUCTION_KIND).hbm_bw == 819e9
    with pytest.raises(KeyError, match="no peak rates"):
        peaks("TPU v4")


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/cache"])
def test_compile_cache_placement(monkeypatch, env_dir):
    """``JAX_COMPILATION_CACHE_DIR`` wins untouched; without it the cache
    goes to the checkout's one fixed directory."""
    import jax
    from repro.launch import device
    set_dirs = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: set_dirs.append((name, value)))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert device.enable_compile_cache() == str(device.CACHE_DIR)
        assert set_dirs == [("jax_compilation_cache_dir",
                             str(device.CACHE_DIR))]
        assert device.CACHE_DIR == pathlib.Path(REPO) / ".jax_cache"
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert device.enable_compile_cache() == env_dir
        assert set_dirs == []
