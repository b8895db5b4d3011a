"""Readings from which a ``train_offload`` cell's limits are set (not part
of a run): ``calibrate.py``'s train readings, through the host-held
committee of ``harness/train_offload.py``.

    python3 bench/calibrate_offload.py --workload <cell> --seeds 1,2,... \
        [--control-seeds 7,8] [--control-dtype float8_e4m3fn] \
        [--fault-seeds 7,8]

For every ``--seeds`` seed the program's numbers against the reference
(the lower readings); for every ``--control-seeds`` seed the reference
itself computed in ``--control-dtype``, the precision below the
configuration's bfloat16, in the program's place (the upper readings);
for every ``--fault-seeds`` seed the reference with half of every
sequence left out.  One JSON line per reading, then one summary line:
the largest program reading and the smallest control and fault reading
of each number.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from harness import common  # noqa: E402

common.set_up_process()

KEYS = ("loss_gap", "agg_norm_gap", "update_norm_gap")


def _seeds(s: str) -> list:
    return [int(x) for x in s.split(",") if x]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--control-dtype", default="float8_e4m3fn")
    ap.add_argument("--fault-seeds", type=_seeds, default=[])
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from harness import train as t
    from harness import train_offload as off

    w = common.workload(args.workload)
    cell = t.Cell(common.config(w["config"]), common.traffic(w["traffic"]))
    out = {"program": [], "control": [], "half_batch": []}
    refs = {}

    def host_pool(seed):
        pool = cell.feed(seed)
        return [jax.device_get(b) for b in pool[:t.CHECK_STEPS]], pool

    for seed in args.seeds:
        host, pool = host_pool(seed)
        params, state, got = cell.check_steps(seed, pool)
        del params, state, pool
        gc.collect()
        refs[seed] = (off.reference_readings(cell, seed, host), host)
        out["program"].append(t.compare(got, refs[seed][0]))
        print(json.dumps({"kind": "program", "seed": seed,
                          **out["program"][-1]}), flush=True)
    for kind, seeds, kw in (
            ("control", args.control_seeds,
             {"dtype": jnp.dtype(args.control_dtype)}),
            ("half_batch", args.fault_seeds, {"half_batch": True})):
        for seed in seeds:
            if seed not in refs:
                host, pool = host_pool(seed)
                del pool
                refs[seed] = (off.reference_readings(cell, seed, host), host)
            ref, host = refs[seed]
            nums = t.compare(off.reference_readings(cell, seed, host, **kw),
                             ref)
            out[kind].append(nums)
            print(json.dumps({"kind": kind, "seed": seed, **nums}),
                  flush=True)
    summary = {f"{kind}.{k}": (max if kind == "program" else min)(
        r[k] for r in rows) for kind, rows in out.items() if rows
        for k in KEYS}
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
