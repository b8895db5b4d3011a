"""Readings from which a cell's limits are set (not part of a run).

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \
        [--control-seeds 7,8,9] [--fault-seeds 7,8,9] [--seconds 8]

For every ``--seeds`` seed the program's numbers against the reference
(the lower readings); for every ``--control-seeds`` seed the control's:
the reference itself in the precision below the configuration's, in the
program's place (the upper readings); for every ``--fault-seeds`` seed,
train cells only, the reference with half of every sequence left out.
One JSON line per reading, then one summary line: the largest program
reading and the smallest control and fault reading of each number.  The
program is compiled once for all seeds; serve cells drive a window of
``--seconds`` per program seed.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from harness import common  # noqa: E402

common.set_up_process()


def _seeds(s: str) -> list:
    return [int(x) for x in s.split(",") if x]


def _line(kind, seed, nums):
    print(json.dumps({"kind": kind, "seed": seed, **nums}), flush=True)


def train(w, cfg, mix, args):
    import gc

    import jax
    import jax.numpy as jnp

    from harness import train as t

    cell = t.Cell(cfg, mix)
    keys = ("loss_gap", "agg_norm_gap", "update_norm_gap")
    out = {"program": [], "control": [], "half_batch": []}
    refs = {}
    for seed in args.seeds:
        pool = cell.feed(seed)
        host = [jax.device_get(b) for b in pool[:t.CHECK_STEPS]]
        params, state, got = cell.check_steps(seed, pool)
        del params, state, pool
        gc.collect()
        refs[seed] = (t.reference_readings(cell, seed, host), host)
        nums = t.compare(got, refs[seed][0])
        _line("program", seed, nums)
        out["program"].append(nums)
    for kind, seeds, kw in (("control", args.control_seeds,
                             {"dtype": jnp.bfloat16}),
                            ("half_batch", args.fault_seeds,
                             {"half_batch": True})):
        for seed in seeds:
            if seed not in refs:
                pool = cell.feed(seed)
                host = [jax.device_get(b) for b in pool[:t.CHECK_STEPS]]
                del pool
                refs[seed] = (t.reference_readings(cell, seed, host), host)
            ref, host = refs[seed]
            nums = t.compare(t.reference_readings(cell, seed, host, **kw),
                             ref)
            _line(kind, seed, nums)
            out[kind].append(nums)
    return keys, out


def serve(w, cfg, mix, args):
    from harness import serve as s

    if args.rates:
        return s.sweep(cfg, mix, args)
    return s.calibrate(w, cfg, mix, args)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--fault-seeds", type=_seeds, default=[])
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", default="",
                    help="serve cells: offered rates to sweep instead")
    args = ap.parse_args(argv)
    w = common.workload(args.workload)
    cfg = common.config(w["config"])
    mix = common.traffic(w["traffic"])
    keys, out = {"train": train, "serve": serve}[mix["kind"]](
        w, cfg, mix, args)
    summary = {}
    for k in keys:
        for kind, rows in out.items():
            if rows:
                vals = [r[k] for r in rows]
                summary[f"{kind}.{k}"] = (max(vals) if kind == "program"
                                          else min(vals))
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
