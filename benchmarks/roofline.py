"""Roofline table: dry-run artifacts + aggregation-backend byte models.

Two row families:

* ``roofline/<arch>.<shape>.<pod>`` — the historic rows read from
  artifacts/dryrun/*.json (produced by repro.launch.sweep): the three
  roofline terms, the dominant one, and the MODEL_FLOPS / HLO_FLOPS
  utilization ratio (EXPERIMENTS.md §Roofline).

* ``roofline/agg.n{n}.f{f}.d{d}`` — the aggregation hot path at the
  paper's production committee (n = 39 = 4f + 3, Fig 4-6) per distance
  backend, from *itemized HBM-byte models* (every term printed in the
  derived column, so the claimed step-times are auditable):

    xla     tensordot distances + the coordinate phase as one fused
            sweep over the theta selected rows (sorting network and
            prefix-sum window; nothing round-trips HBM);
    pallas  kernel pair: tiled Gram + fused coordinate kernel; the
            (theta, d) gather still materializes between them;
    fused   the megakernel (``repro.kernels.fused_agg``): two input
            sweeps, one (d,) write — nothing else touches HBM.

  Step-time = max(bytes / HBM bandwidth, flops / peak) on the published
  peaks of the target chip (``repro.launch.device.peaks``; a v5e for the
  modeled rows, the attached chip's own kind for the measured ones); all
  three backends are memory-bound at production d, so the byte ratio is
  the speedup.  Wall-clock rows are measured only on TPU — off-TPU the
  Pallas kernels run in the pure-Python interpreter, so the rows emit
  ``skipped=interpret-mode-cpu`` (same convention as gar_throughput).

CLI: ``python -m benchmarks.roofline [--quick]`` — ``--quick`` keeps the
smallest d and skips the wall-clock attempts (the CI smoke invocation).
"""
from __future__ import annotations

import argparse
import glob
import json
import os
from typing import Dict, Optional, Sequence

from benchmarks.common import emit
from repro.launch.device import PRODUCTION_KIND, peaks

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts", "dryrun")

#: production aggregation shape: the paper's Fig 4-6 committee
AGG_N, AGG_F = 39, 9

BF16, F32 = 2, 4


def _agg_bytes(backend: str, n: int, f: int, d: int) -> Dict[str, float]:
    """Itemized HBM traffic (bytes) of one bulyan-krum aggregation.

    Inputs stream bf16 (the production HBM format), intermediates that
    round-trip HBM are fp32 (the accumulation contract), n-sized terms
    (the (n, n) matrix, scores) are dropped as O(n^2) << O(n d).
    """
    theta = n - 2 * f
    if backend == "fused":
        return {
            # phase 0 (distance sweep) + phase 1 (combine) each re-read
            # the full worker stack; selection runs on VMEM residents
            "read_grads_2sweeps": 2 * n * d * BF16,
            "write_agg": d * F32,
        }
    if backend == "pallas":
        return {
            "gram_read_grads": n * d * BF16,
            "gather_read_theta": theta * d * BF16,
            "gather_write_f32": theta * d * F32,
            "select_read_stack": theta * d * F32,
            "write_agg": d * F32,
        }
    if backend == "xla":
        return {
            "dist_read_grads": n * d * BF16,
            "coord_read_theta": theta * d * BF16,
            "write_agg": d * F32,
        }
    raise KeyError(f"unknown backend {backend!r}")


def _agg_flops(n: int, d: int) -> float:
    """MXU flops of the Gram contraction (the only matmul-shaped term);
    the VPU sort/window work is bandwidth-limited by construction."""
    return 2.0 * n * n * d


def rows(art_dir: str = ART):
    out = []
    for path in sorted(glob.glob(os.path.join(art_dir, "*.json"))):
        rec = json.load(open(path))
        out.append(rec)
    return out


def main_artifacts(art_dir: str = ART) -> None:
    """The historic dry-run artifact rows (unchanged format)."""
    n_ok = n_skip = n_err = 0
    for rec in rows(art_dir):
        tag = f"{rec.get('arch')}.{rec.get('shape')}" + (
            ".pod2" if rec.get("multi_pod") else ".pod1")
        if rec.get("skipped"):
            n_skip += 1
            emit(f"roofline/{tag}", 0, "skipped(n/a)")
            continue
        if "error" in rec:
            n_err += 1
            emit(f"roofline/{tag}", 0, "ERROR")
            continue
        n_ok += 1
        r = rec["roofline"]
        step_time = max(r["compute_s"], r["memory_s"], r["collective_s"])
        emit(f"roofline/{tag}", 1e6 * step_time,
             f"compute={r['compute_s']:.4f}s;memory={r['memory_s']:.4f}s;"
             f"collective={r['collective_s']:.4f}s;"
             f"dominant={r['dominant'].replace('_s','')};"
             f"useful_ratio={r['useful_flops_ratio'] and round(r['useful_flops_ratio'], 3)}")
    emit("roofline/summary", 0, f"ok={n_ok};skipped={n_skip};errors={n_err}")


def main_agg_backends(ds: Sequence[int] = (1_000_000, 100_000_000),
                      measure: bool = True) -> None:
    """Aggregation-backend roofline rows at the production (n, f).

    Args:
      ds: coordinate counts to model (production models are the large
        end; the small end sanity-checks against the measured rows).
      measure: attempt wall-clock rows (TPU-only; off-TPU they emit
        ``skipped=interpret-mode-cpu``).
    """
    import jax

    n, f = AGG_N, AGG_F
    on_tpu = measure and jax.default_backend() == "tpu"
    chip = peaks(jax.devices()[0].device_kind if on_tpu else PRODUCTION_KIND)
    for d in ds:
        ref_us: Dict[str, float] = {}
        for backend in ("xla", "pallas", "fused"):
            items = _agg_bytes(backend, n, f, d)
            total = sum(items.values())
            mem_s = total / chip.hbm_bw
            comp_s = _agg_flops(n, d) / chip.flops
            us = 1e6 * max(mem_s, comp_s)
            ref_us[backend] = us
            itemized = ";".join(f"{k}={v / d:.0f}d" for k, v in
                                sorted(items.items()))
            speed = (f";speedup_vs_xla={ref_us['xla'] / us:.2f}"
                     if backend != "xla" else "")
            emit(f"roofline/agg.n{n}.f{f}.d{d}", us,
                 f"bytes_total={total / d:.0f}d;{itemized};"
                 f"bound={'mem' if mem_s >= comp_s else 'mxu'}{speed}",
                 backend)
        if not measure:
            continue
        if not on_tpu:
            for backend in ("xla", "pallas", "fused"):
                emit(f"roofline/agg.n{n}.f{f}.d{d}.measured", 0,
                     "skipped=interpret-mode-cpu", backend)
            continue
        import time

        import jax.numpy as jnp
        from repro.dist.robust import distributed_aggregate
        g = {"w": jax.random.normal(jax.random.PRNGKey(0), (n, d),
                                    jnp.bfloat16)}
        for backend in ("xla", "pallas", "fused"):
            fn = jax.jit(lambda t, b=backend: distributed_aggregate(
                t, f, "bulyan-krum", distance_backend=b)[0])
            jax.block_until_ready(fn(g))          # compile
            t0 = time.perf_counter()
            reps = 5
            for _ in range(reps):
                out = fn(g)
            jax.block_until_ready(out)
            us = 1e6 * (time.perf_counter() - t0) / reps
            emit(f"roofline/agg.n{n}.f{f}.d{d}.measured", us,
                 f"model_us={ref_us[backend]:.0f}", backend)


def main(argv: Optional[Sequence[str]] = None) -> None:
    """CLI entry: artifact rows + aggregation-backend rows.

    Args:
      argv: command-line arguments (``None`` = ``sys.argv[1:]``);
        ``--quick`` keeps the smallest modeled d and skips wall-clock
        measurement (the CI smoke run).
    """
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--quick", action="store_true",
                    help="smallest d only, no wall-clock attempts")
    args = ap.parse_args(argv)
    main_artifacts()
    if args.quick:
        main_agg_backends(ds=(1_000_000,), measure=False)
    else:
        main_agg_backends()


if __name__ == "__main__":
    main()
