"""Benchmark harness: one module per paper table/figure plus the systems
benches.  Prints ``name,backend,us_per_call,derived`` CSV rows — the
``backend`` column tags distance-backend comparison rows (xla/pallas)
and the sync-vs-async runtime rows of ``gar_async`` (sync/async);
``-`` marks backend-independent benches.

    PYTHONPATH=src python -m benchmarks.run [--quick|--full] [--only NAME]

Alongside the CSV stream, every bench writes a reproducibility artifact
``benchmarks/artifacts/BENCH_<name>.json`` carrying its parsed rows plus
the environment (jax version, backend, device/host counts, python) and
the effective seed — enough to pin down *which* machine and RNG stream
produced a row when two runs disagree.  ``--no-artifacts`` disables the
writes (e.g. on read-only checkouts).

A bench that raises is reported as a ``<name>/ERROR`` row and the others
still run; the harness then exits non-zero, naming every failed bench.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import pathlib
import platform
import sys
import time
import traceback

ARTIFACTS = pathlib.Path(__file__).resolve().parent / "artifacts"


def bench_env() -> dict:
    """Environment fingerprint stamped into every ``BENCH_*.json``."""
    import jax
    return {
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "devices": str(jax.devices()[0]),
        "python": platform.python_version(),
        "platform": platform.platform(),
    }


def _parse_rows(text: str) -> list:
    """CSV-looking ``name,backend,us,derived`` lines -> row dicts."""
    rows = []
    for line in text.splitlines():
        parts = line.split(",", 3)
        if len(parts) != 4 or " " in parts[0]:
            continue
        name, backend, us, derived = parts
        try:
            us_val = float(us)
        except ValueError:
            continue
        rows.append({"name": name, "backend": backend,
                     "us_per_call": us_val, "derived": derived})
    return rows


def write_artifact(name: str, rows: list, *, seed, env: dict,
                   wall_s: float, extra: dict = None) -> pathlib.Path:
    """Write ``BENCH_<name>.json`` and return its path."""
    ARTIFACTS.mkdir(parents=True, exist_ok=True)
    path = ARTIFACTS / f"BENCH_{name}.json"
    doc = {"bench": name, "seed": seed, "wall_s": round(wall_s, 3),
           "env": env, "rows": rows}
    if extra:
        doc.update(extra)
    with open(path, "w") as fh:
        json.dump(doc, fh, indent=1)
    return path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="longer training runs (closer to the paper's "
                         "epoch counts)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--seed", type=int, default=None,
                    help="override the default PRNG seed of the benches "
                         "that thread one (leeway, gar_async) — rows "
                         "become a pure function of the seed")
    ap.add_argument("--no-artifacts", action="store_true",
                    help="skip the BENCH_<name>.json artifact writes")
    args = ap.parse_args()

    from repro.launch.device import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (fig2_mnist_attack, fig3_cifar_attack,
                            fig45_bulyan_defense, fig6_bulyan_cost,
                            gar_async, gar_reputation, gar_throughput,
                            leeway_scaling, obs_overhead, roofline,
                            serve_robust)

    steps2 = 400 if args.full else 120
    steps3 = 200 if args.full else 50
    steps45 = 400 if args.full else 120
    steps6 = 150 if args.full else 60
    steps_async = 120 if args.full else 60
    steps_rep = 120 if args.full else 40
    seeded = {} if args.seed is None else {"seed": args.seed}

    benches = [
        ("leeway", lambda: leeway_scaling.main(**seeded)),
        ("gar_throughput", lambda: gar_throughput.main()),
        ("gar_throughput_dist", lambda: gar_throughput.main_dist()),
        ("gar_backends", lambda: gar_throughput.main_backends()),
        ("gar_buffered", lambda: gar_throughput.main_buffered()),
        ("gar_async", lambda: gar_async.main(steps=steps_async,
                                             **seeded)),
        ("gar_reputation", lambda: gar_reputation.main(steps=steps_rep,
                                                       **seeded)),
        ("serve_robust", lambda: serve_robust.main()),
        ("serve_speculative", lambda: serve_robust.main_speculative()),
        ("obs_overhead", lambda: obs_overhead.main()),
        ("fig2", lambda: fig2_mnist_attack.main(steps=steps2)),
        ("fig3", lambda: fig3_cifar_attack.main(steps=steps3)),
        ("fig45", lambda: fig45_bulyan_defense.main(steps=steps45)),
        ("fig6", lambda: fig6_bulyan_cost.main(steps=steps6)),
        ("roofline", lambda: roofline.main()),
    ]
    env = bench_env()
    failed = []
    print("name,backend,us_per_call,derived")
    for name, fn in benches:
        if args.only and args.only != name:
            continue
        t0 = time.time()
        buf = io.StringIO()
        err = None
        # tee: rows stream to the terminal unchanged AND get captured
        # for the JSON artifact
        try:
            with contextlib.redirect_stdout(buf):
                fn()
        except Exception as e:  # keep the harness going; exit code below
            err = f"{type(e).__name__}:{e}"
            failed.append(name)
            traceback.print_exc()
        captured = buf.getvalue()
        sys.stdout.write(captured)
        if err:
            print(f"{name}/ERROR,-,0,{err}", flush=True)
        wall = time.time() - t0
        print(f"{name}/total,-,{1e6 * wall:.0f},done", flush=True)
        if not args.no_artifacts:
            rows = _parse_rows(captured)
            extra = {"error": err} if err else None
            write_artifact(name, rows, seed=args.seed, env=env,
                           wall_s=wall, extra=extra)
    if failed:
        sys.exit(f"benches failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
