"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``<cell>`` is a ``workloads`` entry of ``BENCHMARK.json``; everything it
is made of is found by name under ``bench/`` (see ``harness/common.py``).
The run builds its inputs and weights from ``--seed``, warms up every
shape it uses, measures for ``--seconds`` with tracing off, and with
``--trace 1`` then traces a few more seconds and reduces the trace to
the cell's per-layer metrics.  After the window it checks what the timed
path produced against the plain reference under ``bench/reference/``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer ones), ``device``, with ``--trace
1`` a ``breakdown``, and last ``checks``: each number compared beside
its limit, which also close standard error.  Without a TPU, or with
fewer chips than the cell asks for, it prints no result and exits 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from harness import common  # noqa: E402

common.set_up_process()


def main(argv=None, *, require_tpu: bool = True, step_factory=None,
         serve_hook=None) -> int:
    """Run a cell; ``require_tpu``, ``step_factory`` and ``serve_hook``
    exist for the benchmark's own tests, which drive a run on the CPU
    with a fault planted under the timed path."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    w = common.workload(args.workload)
    cfg = common.config(w["config"])
    mix = common.traffic(w["traffic"])
    limits = common.load_json(common.BENCH / "limits" / f"{w['name']}.json")

    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        print(f"bench: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < w["chips"]:
        print(f"bench: {w['name']} needs {w['chips']} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    devices = devices[:w["chips"]]

    from harness.peaks import peaks

    pk = peaks(devices[0].device_kind) if args.trace else None
    driver = common.module("harness", mix["kind"])
    hooks = {"step_factory": step_factory} if mix["kind"] == "train" \
        else {"hook": serve_hook}
    result, checks, e2e, red, breakdown, extra = driver.run(
        w["name"], cfg, mix, limits, args.seed, args.seconds,
        bool(args.trace), devices, T_START, **hooks)

    for k, v in extra.items():
        print(f"{k}: {v!r}", file=sys.stderr)
    if args.trace:
        ctx = {"cell": w["name"], "cfg": cfg, "mix": mix, "trace": red,
               "peaks": pk, "chips": len(devices), "e2e": e2e}
        for m in common.metrics_of(w["name"], "per_layer"):
            value = common.module("metrics", m["name"]).read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["device"]["busy_s"] = red["busy_s"]
        result["device"]["window_s"] = red["window_s"]
        result["breakdown"] = breakdown
    else:
        for m in common.metrics_of(w["name"], "end_to_end"):
            value, unit = e2e[m["name"]]
            result["metrics"][m["name"]] = {"value": value, "unit": unit}
    common.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
