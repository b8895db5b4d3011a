import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch x input shape) on the
production meshes, and extract the roofline terms from the compiled
artifact.

MUST be run as its own process (`python -m repro.launch.dryrun ...`): the
XLA_FLAGS and JAX_PLATFORMS overrides above execute before any other
import so that jax initializes with 512 host placeholder devices and
never claims an attached accelerator.  Smoke tests and benchmarks never
import this module.

Outputs a JSON artifact per run with:
  memory_analysis   bytes per device (argument/output/temp/generated code)
  cost_analysis     HLO flops / bytes accessed
  collectives       per-op-kind byte totals parsed from the compiled HLO
  roofline          the three terms (compute/memory/collective, seconds)
                    against the peaks of the production chip, the
                    dominant term, and the MODEL_FLOPS / HLO_FLOPS
                    utilization ratio
"""
import argparse
import json
import re
import time
from typing import Any, Dict, Optional

from repro.launch.device import PRODUCTION_KIND, peaks

TARGET = peaks(PRODUCTION_KIND)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_TYPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _TYPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str, top_k: int = 12):
    """Sum result-shape bytes of every collective op in the HLO.

    Convention: we count the *result* shape of each op (= operand shape for
    all-reduce / collective-permute; the gathered size for all-gather; the
    scattered size for reduce-scatter).  Counts are per-instruction in the
    SPMD module, i.e. per-device traffic.  Also returns the ``top_k``
    largest individual collective ops (kind, bytes, result type) for the
    perf-iteration loop."""
    out: Dict[str, Dict[str, float]] = {
        k: {"bytes": 0, "count": 0} for k in _COLLECTIVES}
    ops = []
    for line in hlo_text.splitlines():
        for kind in _COLLECTIVES:
            # match "= TYPE kind(" including tuple types, and -start forms
            m = re.search(r"=\s+(.*?)\s+" + kind + r"(-start)?\(", line)
            if m:
                b = _shape_bytes(m.group(1))
                out[kind]["bytes"] += b
                out[kind]["count"] += 1
                ops.append((kind, b, m.group(1)[:120]))
                break
    ops.sort(key=lambda t: -t[1])
    return out, [{"kind": k, "bytes": b, "type": t}
                 for k, b, t in ops[:top_k]]


def _cost_dict(compiled) -> Dict[str, float]:
    try:
        ca = compiled.cost_analysis()
    except Exception as e:  # pragma: no cover
        return {"error": str(e)}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return {k: float(v) for k, v in dict(ca).items()
            if isinstance(v, (int, float))}


def _memory_dict(compiled) -> Dict[str, Any]:
    try:
        ma = compiled.memory_analysis()
    except Exception as e:  # pragma: no cover
        return {"error": str(e)}
    if ma is None:
        return {"unavailable": True}
    out = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        if hasattr(ma, attr):
            out[attr] = int(getattr(ma, attr))
    return out


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6 N D for training, 2 N_active D for inference."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    return 2.0 * n_active * shape.global_batch  # decode: one token/seq


def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            gar: str = "bulyan-krum", attack: str = "none",
            reduced: bool = False, impl: str = "auto",
            optimizer_name: str = "momentum", moe_impl: Optional[str] = None,
            param_dtype: Optional[str] = None, agg_dtype: str = "native",
            distance_backend: str = "auto", unroll: bool = False,
            rep_lr: Optional[float] = None,
            async_tau: Optional[int] = None, async_schedule: str = "fixed",
            attn_shard: Optional[str] = None,
            logits_dtype: Optional[str] = None,
            serve_gar: Optional[str] = None, serve_f: int = 2,
            serve_replicas: int = 0, serve_speculative_k: int = 0,
            telemetry: bool = False,
            out_path: Optional[str] = None) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from repro.agg import quorum
    from repro.configs import get_config, get_reduced, shape_applicable
    from repro.dist.async_train import (init_async_state,
                                        make_async_train_step)
    from repro.dist.mesh import make_production_mesh
    from repro.dist.serve import make_prefill_step, make_serve_step
    from repro.dist.serve_robust import (init_ensemble_state,
                                         make_robust_serve_step,
                                         make_robust_verify_step)
    from repro.agg import AggSpec
    from repro.dist.train import init_agg_state, make_train_step
    from repro.launch import specs as S
    from repro.models.config import INPUT_SHAPES
    from repro.optim import get_optimizer

    assert jax.device_count() == 512, (
        "dryrun must own the process (512 host devices); run via "
        "python -m repro.launch.dryrun")

    if not shape_applicable(arch, shape_name):
        rec = {"arch": arch, "shape": shape_name, "skipped": True,
               "reason": "long_500k not applicable (see DESIGN.md §6)"}
        if out_path:
            with open(out_path, "w") as fh:
                json.dump(rec, fh, indent=1)
        return rec

    import dataclasses

    cfg = get_reduced(arch) if reduced else get_config(arch)
    overrides = {}
    if moe_impl:
        overrides["moe_impl"] = moe_impl
    if param_dtype:
        overrides["param_dtype"] = param_dtype
    if unroll:
        overrides["unroll_scan"] = True
    if attn_shard:
        overrides["attn_shard"] = attn_shard
    if logits_dtype:
        overrides["logits_dtype"] = logits_dtype
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    record: Dict[str, Any] = {
        "arch": arch, "shape": shape_name,
        "mesh": "x".join(map(str, mesh.devices.shape)),
        "multi_pod": multi_pod, "gar": gar, "attack": attack,
        "reduced": reduced, "impl": impl, "overrides": overrides,
        "agg_dtype": agg_dtype, "distance_backend": distance_backend,
        "telemetry": telemetry,
    }
    n_chips = mesh.devices.size
    t0 = time.time()

    with jax.set_mesh(mesh):
        params, param_sh = S.param_specs(cfg, mesh)
        inputs = S.input_specs(cfg, shape_name, mesh)

        if shape.kind == "train" and async_tau is not None:
            # asynchronous bounded-staleness train step: the GradientBus
            # (per-worker versioned slots) rides in the carried AggState,
            # initialized abstractly so nothing is materialized
            opt = get_optimizer(optimizer_name, 1e-3)
            opt_state, opt_sh = S.opt_specs(params, opt, mesh)
            spec = AggSpec(f=3, gar=gar, attack=attack,
                           agg_dtype=agg_dtype,
                           distance_backend=distance_backend,
                           rep_lr=rep_lr,
                           async_tau=async_tau,
                           async_schedule=async_schedule,
                           telemetry=telemetry)
            record.update(async_tau=async_tau,
                          async_schedule=async_schedule)
            if rep_lr is not None:
                record.update(rep_lr=rep_lr)
            step = make_async_train_step(cfg, spec, opt, impl=impl,
                                         mesh=mesh)
            n_workers = inputs["tokens"].shape[0]
            agg_state = jax.eval_shape(
                lambda: init_async_state(spec, params, n_workers))
            jitted = jax.jit(step, donate_argnums=(0, 1),
                             out_shardings=(param_sh, opt_sh, None, None))
            lowered = jitted.lower(params, opt_state, inputs, agg_state)
        elif shape.kind == "train":
            opt = get_optimizer(optimizer_name, 1e-3)
            opt_state, opt_sh = S.opt_specs(params, opt, mesh)
            spec = AggSpec(f=3, gar=gar, attack=attack,
                           agg_dtype=agg_dtype,
                           distance_backend=distance_backend,
                           rep_lr=rep_lr,
                           telemetry=telemetry)
            if rep_lr is not None:
                record.update(rep_lr=rep_lr)
            step = make_train_step(cfg, spec, opt, impl=impl, mesh=mesh)
            if spec.rule().stateful:
                # abstract AggState: eval_shape keeps the (W, n, ...)
                # history buffers as structs — nothing is materialized
                n_workers = inputs["tokens"].shape[0]
                agg_state = jax.eval_shape(
                    lambda: init_agg_state(spec, params, n_workers))
                jitted = jax.jit(step, donate_argnums=(0, 1),
                                 out_shardings=(param_sh, opt_sh, None,
                                                None))
                lowered = jitted.lower(params, opt_state, inputs, agg_state)
            else:
                jitted = jax.jit(step, donate_argnums=(0, 1),
                                 out_shardings=(param_sh, opt_sh, None))
                lowered = jitted.lower(params, opt_state, inputs)
        elif shape.kind == "prefill":
            step = make_prefill_step(cfg, impl=impl)
            jitted = jax.jit(step)
            args = [params, inputs["tokens"]]
            if "extra" in inputs:
                args.append(inputs["extra"])
            lowered = jitted.lower(*args)
        elif shape.kind == "decode" and serve_gar:
            # robust ensemble decode: replica-stacked params/caches with
            # the replica axis on ``data``, per-token logits aggregation
            # through the registry (repro.dist.serve_robust)
            n_rep = serve_replicas or quorum(serve_gar, serve_f)
            sspec = AggSpec(f=serve_f, gar=serve_gar,
                            agg_dtype=agg_dtype,
                            distance_backend=distance_backend,
                            speculative_k=serve_speculative_k,
                            telemetry=telemetry)
            record.update(serve_gar=serve_gar, serve_f=serve_f,
                          serve_replicas=n_rep,
                          serve_speculative_k=serve_speculative_k)
            eparams, _ = S.ensemble_param_specs(cfg, mesh, n_rep)
            cache, cache_sh = S.ensemble_cache_specs(
                cfg, n_rep, shape.global_batch, shape.seq_len, mesh)
            agg_state = None
            if sspec.rule().stateful:
                agg_state = jax.eval_shape(
                    lambda: init_ensemble_state(sspec, n_rep,
                                                shape.global_batch,
                                                cfg.vocab_size))
            if serve_speculative_k >= 1:
                # speculative verify: the whole (B, k) draft block through
                # one batched robust-aggregation step, per-slot positions
                from jax.sharding import PartitionSpec as P
                b = shape.global_batch
                block = S.sds((b, serve_speculative_k), jnp.int32, mesh,
                              inputs["token"].sharding.spec)
                posv = S.sds((b,), jnp.int32, mesh, P())
                step = make_robust_verify_step(cfg, sspec, mesh=mesh)
                jitted = jax.jit(step, donate_argnums=(1,),
                                 out_shardings=(None, cache_sh, None, None))
                lowered = jitted.lower(eparams, cache, block, posv,
                                       agg_state)
            else:
                step = make_robust_serve_step(cfg, sspec, mesh=mesh)
                jitted = jax.jit(step, donate_argnums=(1,),
                                 out_shardings=(None, cache_sh, None, None))
                lowered = jitted.lower(eparams, cache, inputs["token"],
                                       inputs["pos"], agg_state)
        else:  # decode
            cache, cache_sh = S.cache_specs(cfg, shape.global_batch,
                                            shape.seq_len, mesh)
            step = make_serve_step(cfg)
            jitted = jax.jit(step, donate_argnums=(1,),
                             out_shardings=(None, cache_sh))
            lowered = jitted.lower(params, cache, inputs["token"],
                                   inputs["pos"])

        record["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        record["compile_s"] = round(time.time() - t1, 1)

    mem = _memory_dict(compiled)
    cost = _cost_dict(compiled)
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = lowered.as_text()
    coll, top_ops = parse_collectives(hlo)
    record["memory_analysis"] = mem
    record["cost_analysis"] = {k: cost[k] for k in
                               ("flops", "bytes accessed")
                               if k in cost} or cost
    record["collectives"] = coll
    record["top_collective_ops"] = top_ops
    record["hlo_lines"] = hlo.count("\n")

    # roofline terms.  cost_analysis on the SPMD module is per-device.
    flops = cost.get("flops", 0.0)
    bytes_acc = cost.get("bytes accessed", 0.0)
    coll_bytes = sum(v["bytes"] for v in coll.values())
    mf = model_flops(cfg, shape)
    compute_t = flops / TARGET.flops
    memory_t = bytes_acc / TARGET.hbm_bw
    coll_t = coll_bytes / TARGET.ici_link_bw
    terms = {"compute_s": compute_t, "memory_s": memory_t,
             "collective_s": coll_t}
    record["roofline"] = {
        **terms,
        "dominant": max(terms, key=terms.get),
        "model_flops_total": mf,
        "model_flops_per_chip": mf / n_chips,
        "hlo_flops_per_chip": flops,
        "useful_flops_ratio": (mf / n_chips) / flops if flops else None,
        "collective_bytes_per_chip": coll_bytes,
    }

    if out_path:
        with open(out_path, "w") as fh:
            json.dump(record, fh, indent=1)
    return record


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True,
                    choices=["train_4k", "prefill_32k", "decode_32k",
                             "long_500k"])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--gar", default="bulyan-krum")
    ap.add_argument("--attack", default="none")
    ap.add_argument("--impl", default="auto",
                    help="attention impl: auto|naive|blockwise")
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced config (fast sanity check)")
    ap.add_argument("--moe-impl", default=None,
                    choices=[None, "einsum", "scatter"],
                    help="override cfg.moe_impl (perf iterations)")
    ap.add_argument("--param-dtype", default=None,
                    choices=[None, "float32", "bfloat16"])
    ap.add_argument("--agg-dtype", default="native",
                    choices=["native", "bfloat16", "float32"],
                    help="gradient dtype for the robust aggregation")
    ap.add_argument("--distance-backend", default="auto",
                    choices=["auto", "xla", "pallas", "fused"],
                    help="pairwise-distance implementation for distance-"
                         "based GARs (pallas = shard-mapped tiled kernel; "
                         "fused = single-sweep megakernel, rules lowered "
                         "onto their fused-<base> composites; "
                         "auto = pallas on TPU, xla elsewhere)")
    ap.add_argument("--rep-lr", type=float, default=None,
                    help="reputation EMA rate for --gar reputation-<base> "
                         "(truthy values also switch on the reputation-"
                         "scaled step size; see repro.agg.reputation)")
    ap.add_argument("--async-tau", type=int, default=None,
                    help="lower the asynchronous bounded-staleness train "
                         "step instead of the synchronous one (train "
                         "shapes only): per-worker staleness bound of "
                         "the GradientBus delay schedule; pair with "
                         "--gar stale-<base> for staleness-weighted "
                         "aggregation (repro.dist.async_train)")
    ap.add_argument("--async-schedule", default="fixed",
                    choices=["fixed", "random"],
                    help="deterministic delay schedule of --async-tau "
                         "(fixed = staggered round-robin, random = "
                         "bounded Bernoulli)")
    ap.add_argument("--expert-gather", action="store_true",
                    help="constrain expert weights to TP-only at use site "
                         "(per-layer all-gather instead of activation "
                         "all-reduce; see repro.models.moe)")
    ap.add_argument("--legacy-sharding", action="store_true",
                    help="pre-iteration param sharding rules (A/B baseline)")
    ap.add_argument("--logits-dtype", default=None,
                    choices=[None, "float32", "bfloat16"])
    ap.add_argument("--serve-gar", default=None,
                    help="robust ensemble decode: aggregate per-token "
                         "replica logits with this GAR (decode shapes "
                         "only; see repro.dist.serve_robust)")
    ap.add_argument("--serve-f", type=int, default=2,
                    help="Byzantine replica bound of --serve-gar")
    ap.add_argument("--serve-speculative-k", type=int, default=0,
                    help="lower the robust speculative verify step for "
                         "(B, k) draft blocks instead of the per-token "
                         "serve step (decode shapes with --serve-gar)")
    ap.add_argument("--serve-replicas", type=int, default=0,
                    help="ensemble size (0 = the rule's minimal quorum "
                         "for --serve-f)")
    ap.add_argument("--attn-shard", default=None,
                    choices=[None, "none", "batch"],
                    help="attention activation sharding (see ModelConfig)")
    ap.add_argument("--unroll", action="store_true",
                    help="unroll the layer scan: analysis-grade costs "
                         "(cost_analysis/HLO parsing see while bodies "
                         "once; rolled runs undercount per-step work)")
    ap.add_argument("--telemetry", action="store_true",
                    help="compile with aggregation forensics on (wraps "
                         "the GAR in its obs-* composite; the carried "
                         "AggState gains a fixed-size metrics ring)")
    ap.add_argument("--out", default=None, help="JSON artifact path")
    args = ap.parse_args()
    if args.legacy_sharding:
        from repro.dist import sharding as _sh
        _sh.LEGACY_RULES = True
    if args.expert_gather:
        from repro.models import moe as _moe
        _moe.EXPERT_WEIGHT_GATHER = True
    rec = run_one(args.arch, args.shape, multi_pod=args.multi_pod,
                  gar=args.gar, attack=args.attack, reduced=args.reduced,
                  impl=args.impl, moe_impl=args.moe_impl,
                  param_dtype=args.param_dtype, agg_dtype=args.agg_dtype,
                  distance_backend=args.distance_backend,
                  rep_lr=args.rep_lr,
                  async_tau=args.async_tau,
                  async_schedule=args.async_schedule,
                  unroll=args.unroll, attn_shard=args.attn_shard,
                  logits_dtype=args.logits_dtype,
                  serve_gar=args.serve_gar, serve_f=args.serve_f,
                  serve_replicas=args.serve_replicas,
                  serve_speculative_k=args.serve_speculative_k,
                  telemetry=args.telemetry,
                  out_path=args.out)
    print(json.dumps(rec, indent=1))


if __name__ == "__main__":
    main()
