"""What every cell shares: where things are, what a cell is made of, the
device it runs on, and the result line.

A cell (``--workload``) names a configuration and a traffic mix in
``BENCHMARK.json``.  Each piece is found by its name alone:

  bench/configs/<config>.json      sizes as run, source, cuts, precision
  bench/families/<model_type>.py   how the program is built for a family
  bench/reference/<reference>.py   the family's plain reference
  bench/traffic/<traffic>.json     the mix, read by the driver of its kind
  bench/harness/<kind>.py          the driver of a kind of traffic
  bench/metrics/<metric>.py        one reader per per-layer metric

so a new cell, configuration, mix or metric is new files and a new entry.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
import pathlib
import sys

#: the checkout's root and the benchmark's own directory
ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
#: JAX's persistent compilation cache: one fixed directory inside the
#: checkout (the path is part of the cache key, so it never moves)
CACHE_DIR = ROOT / ".bench_cache" / "jax"
#: traces and other run-time leftovers, inside the checkout
OUT_DIR = ROOT / ".bench_cache" / "out"


def set_up_process() -> None:
    """Environment for a run; call before JAX is imported.

    The compile cache goes to :data:`CACHE_DIR` whatever the environment
    says, every program is cached however fast it compiled, and the TPU
    runtime writes no logs outside the checkout.
    """
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    os.environ["TPU_LOG_DIR"] = "disabled"
    for p in (str(BENCH), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)


def apply_precision(cfg: dict) -> None:
    """Run the program at the configuration's stated matmul precision
    (``default``, ``high`` or ``highest``) for the rest of the process."""
    import jax

    jax.config.update("jax_default_matmul_precision",
                      cfg["precision"]["matmul_precision"])


def load_json(path: pathlib.Path):
    with open(path) as fh:
        return json.load(fh)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def workload(name: str) -> dict:
    for w in benchmark()["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    return load_json(BENCH / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    if kind in ("reference", "families", "harness"):
        return importlib.import_module(f"{kind}.{name}")
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_of(cell: str, which: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries a cell reports: those
    that list it, and those that list no cells."""
    return [m for m in benchmark()[which]
            if "workloads" not in m or cell in m["workloads"]]


def one_chip(name: str, devices) -> None:
    """The train and serve drivers place all work on the default device:
    a cell on more chips needs a driver of its own, which spreads it."""
    if len(devices) != 1:
        raise ValueError(f"{name}: this driver runs on one chip, the cell "
                         f"asks for {len(devices)}")


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_analysis(compiled) -> dict:
    """A compiled program's own byte counts (what the allocator's peak
    does not show: its temporaries)."""
    ma = compiled.memory_analysis()
    return {k: getattr(ma, f"{k}_size_in_bytes") for k in
            ("argument", "output", "alias", "temp", "generated_code")}


def peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip, as the runtime reports."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def emit(result: dict, checks: dict) -> None:
    """Print every number compared beside its limit as the last lines of
    standard error, then the result line (``checks`` last) as the last
    line of standard output."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    out = dict(result)
    out["checks"] = checks
    print(json.dumps(out), flush=True)
