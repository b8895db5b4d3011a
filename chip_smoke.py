"""Bring-up smoke of both hot paths on the chip, at published widths.

    python chip_smoke.py [--seed N]        # one chip: robust train + serving
    python chip_smoke.py --chips 4         # the 2x2 data x model mesh path

The model is ``llama3_2_3b`` at its published widths (d_model 3072, 24
heads, 8 KV heads, head_dim 128, d_ff 8192), cut only in depth (whole
layers) and in vocabulary (the first eighth of the rows); weights and data
are generated from ``--seed``.

One chip runs two phases through their normal entry points:

  train   ``repro.dist.train.make_train_step`` with a committee of n = 7,
          f = 1, ``bulyan-krum``, the in-graph ``omniscient_linf`` attack
          and AdamW, parameters and optimizer state donated; one step per
          ``distance_backend`` (xla, pallas, fused) from the same state,
          compared, then a few more steps each
  serve   ``ServingEngine`` over a 7-replica ``bulyan-krum`` ensemble whose
          last replica is poisoned, per token and with ``speculative_k=4``,
          compared on aggregated logits with the clean ensemble

``--chips 4`` runs only the mesh path: the same train step on a (2, 2)
``data x model`` mesh with n = 8 workers, for the xla and pallas distance
backends, each compared with the xla step on one device in the same
process.

Any failed check, or a platform other than ``tpu``, exits non-zero.  The
last line of standard output is the JSON record
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import pathlib
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np
from jax.sharding import NamedSharding, SingleDeviceSharding

# the repo runs from its checkout, uninstalled
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

from repro.agg import AggSpec
from repro.configs import get_config
from repro.data.synthetic import lm_batches
from repro.dist.mesh import make_host_mesh
from repro.dist.serve_robust import poison_replicas, replicate_params
from repro.dist.sharding import batch_pspec, param_shardings
from repro.dist.train import make_train_step
from repro.launch.device import enable_compile_cache
from repro.models import init_model
from repro.optim import get_optimizer
from repro.serving import Request, ServingEngine

#: committee of the one-chip train phase (the smallest Bulyan quorum at
#: f = 1, n = 4f + 3) and of the mesh path (n divisible by the data axis)
N_ONE, N_MESH, F = 7, 8, 1
SEQ = 2048                 # tokens per worker, one sequence each
LAYERS = 1                 # deepest cut whose train step fits one chip
VOCAB_CUT = 8              # hold 1/8 of the published vocabulary
STEPS = 3                  # train steps per backend
#: the in-graph attack: the paper's omniscient L-inf adversary, placed at
#: 3x its leeway estimate so that Bulyan's selection must reject it (at
#: 1x it sits inside the honest cluster and is selected by design)
ATTACK = (("margin", 3.0),)
#: agreement of the parameters two programs give after one AdamW step
#: from the same state: a coordinate mismatches when it differs by more
#: than RTOL x its leaf's RMS, and at most MISMATCH_FRAC of all
#: coordinates may.  f32 reassociation moves coordinates by ~1e-6 RMS, one
#: bf16 rounding of the update (2^-9 relative) would move nearly all of
#: them past RTOL, and a different Krum selection would too.  Bulyan's
#: coordinate phase is discontinuous: where two windows lie within the
#: programs' rounding noise of equal distance from the median, the
#: averaged window flips; the parameter then moves differently where the
#: flip changes the sign of the first AdamW update
RTOL, MISMATCH_FRAC = 1e-4, 1e-3
#: serving: a few requests of a few hundred tokens, a few dozen new
PROMPT, NEW_TOKENS, SLOTS, SPEC_K = 320, 32, 4, 4
#: aggregated logits of the poisoned and the clean ensemble: Bulyan drops
#: the poisoned replica, so both aggregate the same honest rows in the
#: same program; only f32 reassociation may separate them
LOGIT_RTOL = 1e-5


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


def llama_cut(layers: int = LAYERS):
    """``llama3_2_3b`` cut in depth and vocabulary only."""
    base = get_config("llama3_2_3b")
    return dataclasses.replace(
        base, name=f"{base.name}-{layers}L-v{base.vocab_size // VOCAB_CUT}",
        n_layers=layers, vocab_size=base.vocab_size // VOCAB_CUT)


def train_spec(backend: str):
    return AggSpec(f=F, gar="bulyan-krum", attack="omniscient_linf",
                   attack_kwargs=ATTACK, distance_backend=backend)


def optimizer():
    return get_optimizer("adamw", 3e-4, weight_decay=0.01)


def train_batch(cfg, n: int, seed: int, step: int) -> dict:
    """One ``(n, 1, SEQ)`` batch of the seeded synthetic LM stream."""
    toks, labs = zip(*(lm_batches(cfg.vocab_size, 1, SEQ, step * n + w,
                                  seed=seed) for w in range(n)))
    return {"tokens": np.stack(toks), "labels": np.stack(labs)}


def describe(compiled) -> str:
    ma = compiled.memory_analysis()
    return (f"argument={ma.argument_size_in_bytes} "
            f"output={ma.output_size_in_bytes} "
            f"alias={ma.alias_size_in_bytes} "
            f"temp={ma.temp_size_in_bytes} "
            f"code={ma.generated_code_size_in_bytes}")


def shapes(tree, sharding):
    """Abstract arguments for lowering: ``tree``'s shapes on ``sharding``
    (one sharding, or a pytree of them matching ``tree``)."""
    if not isinstance(sharding, jax.sharding.Sharding):
        return jax.tree_util.tree_map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, sharding)
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def compile_steps(jobs: dict) -> dict:
    """Lower and compile ``{name: (jitted, args, mesh or None)}``, all at
    once: XLA compiles off the interpreter lock, and the step programs
    take minutes each.  Prints each program's compile seconds and
    memory analysis; returns ``{name: compiled}``."""

    def one(name, jitted, args, mesh):
        t0 = time.perf_counter()
        with (jax.set_mesh(mesh) if mesh is not None
              else contextlib.nullcontext()):
            compiled = jitted.lower(*args).compile()
        print(f"[{name}] compile_s={time.perf_counter() - t0:.2f} "
              f"memory_analysis: {describe(compiled)}", flush=True)
        return compiled

    with ThreadPoolExecutor(len(jobs)) as pool:
        futures = {name: pool.submit(one, name, *job)
                   for name, job in jobs.items()}
        return {name: f.result() for name, f in futures.items()}


def mismatch(a, b) -> tuple:
    """(mismatched coordinates, all coordinates, worst |a-b| / leaf RMS,
    ``{leaf path: mismatched coordinates}`` of the leaves with any) of
    two pytrees of host arrays, per :data:`RTOL`."""
    bad = total = 0
    worst = 0.0
    by_leaf = {}
    for (path, x), y in zip(jax.tree_util.tree_flatten_with_path(a)[0],
                            jax.tree_util.tree_leaves(b)):
        x = np.asarray(x, np.float32)
        y = np.asarray(y, np.float32)
        scale = float(np.sqrt(np.mean(np.square(y)))) or 1.0
        err = np.abs(x - y) / scale
        leaf_bad = int(np.count_nonzero(err > RTOL))
        if leaf_bad:
            by_leaf[jax.tree_util.keystr(path)] = leaf_bad
        bad += leaf_bad
        total += err.size
        worst = max(worst, float(err.max()))
    return bad, total, worst, by_leaf


def agreement(what: str, a, b) -> tuple:
    """Print how far two pytrees differ; return ``(agrees, claim)`` for
    :func:`check`, so that every comparison prints before one fails."""
    bad, total, worst, by_leaf = mismatch(a, b)
    print(f"  {what}: {bad}/{total} coordinates past {RTOL} x RMS, "
          f"worst {worst:.3e} x RMS" + (f"; by leaf {by_leaf}" if by_leaf
                                         else ""), flush=True)
    return (bad <= MISMATCH_FRAC * total,
            f"{what} agree (<= {MISMATCH_FRAC} of coordinates past "
            f"{RTOL} x RMS)")


def same_loss(what: str, got: dict, ref: dict) -> tuple:
    a, b = float(got["loss"]), float(ref["loss"])
    return abs(a - b) <= 1e-5 * abs(b), f"{what} loss equals ({a} vs {b})"


def check_metrics(m: dict, step: int) -> None:
    print(f"  step {step}: " + " ".join(f"{k}={float(v):.6g}"
                                        for k, v in sorted(m.items())),
          flush=True)
    check(np.isfinite(m["loss"]) and np.isfinite(m["grad_norm"]),
          f"step {step}: loss and grad_norm finite")
    check(float(m["byz_weight"]) == 0.0,
          f"step {step}: Bulyan selected no Byzantine row (byz_weight 0)")


def peak_bytes(dev) -> int:
    return dev.memory_stats()["peak_bytes_in_use"]


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def train_phase(cfg, seed: int, dev) -> None:
    """The robust train step on one chip, once per distance backend."""
    print(f"== train: n={N_ONE} f={F} bulyan-krum, omniscient_linf "
          f"{dict(ATTACK)}, AdamW, 1x{SEQ} tokens per worker", flush=True)
    opt = optimizer()
    params = init_model(jax.random.PRNGKey(seed), cfg)
    state0 = jax.device_get((params, opt.init(params)))
    del params
    batches = [train_batch(cfg, N_ONE, seed, t) for t in range(STEPS)]
    one_dev = SingleDeviceSharding(dev)
    args = (shapes(state0[0], one_dev), shapes(state0[1], one_dev),
            shapes(batches[0], one_dev))
    backends = ("xla", "pallas", "fused")
    programs = compile_steps({
        f"train/{b}": (jax.jit(make_train_step(cfg, train_spec(b), opt),
                               donate_argnums=(0, 1)), args, None)
        for b in backends})
    first = {}
    for backend in backends:
        compiled = programs.pop(f"train/{backend}")
        if backend != "xla":
            check("tpu_custom_call" in compiled.as_text(),
                  f"{backend} step runs its Pallas kernels compiled "
                  f"(tpu_custom_call in the program)")
        params, state = jax.device_put(state0, dev)
        for t, batch in enumerate(batches):
            params, state, m = compiled(params, state, batch)
            if t == 0:
                first[backend] = jax.device_get((params, m))
            check_metrics(jax.device_get(m), t)
        del params, state
        print(f"  peak_bytes_in_use={peak_bytes(dev)}", flush=True)
    ref_p, ref_metrics = first["xla"]
    verdicts = []
    for backend in ("pallas", "fused"):
        p, metrics = first[backend]
        verdicts += [
            same_loss(f"{backend} vs xla", metrics, ref_metrics),
            agreement(f"{backend} vs xla updated parameters", p, ref_p)]
    for verdict in verdicts:
        check(*verdict)


def record(fn, sink: list):
    """Wrap an engine step to keep the aggregated logits and the
    selection weights (outputs 0 and 2 of prefill, decode and verify)."""
    def wrapped(*args):
        out = fn(*args)
        sink.append((out[0], out[2].selected))
        return out
    return wrapped


def serve_run(stacked, cfg, spec, prompts) -> dict:
    """Serve ``prompts`` through ``ServingEngine``; keep the aggregated
    logits and selection weights of every prefill, decode or verify."""
    t0 = time.perf_counter()
    engine = ServingEngine(stacked, cfg, n_slots=SLOTS,
                           cache_len=PROMPT + NEW_TOKENS + SPEC_K,
                           ensemble=spec)
    seen = []
    engine._prefill = record(engine._prefill, seen)
    if spec.speculative_k:
        engine._verify = record(engine._verify, seen)
    else:
        engine._decode = record(engine._decode, seen)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=NEW_TOKENS)
            for i, p in enumerate(prompts)]
    out = engine.run(reqs)
    wall = time.perf_counter() - t0
    logits, selected = zip(*jax.device_get(seen))
    return {"tokens": out, "logits": logits, "selected": selected,
            "wall_s": wall}


def serve_phase(cfg, seed: int, dev) -> None:
    """Robust ensemble serving, per token and speculative, poisoned vs
    clean."""
    print(f"== serve: {N_ONE} replicas, bulyan-krum f={F}, last replica "
          f"signflip x10; {SLOTS} requests of {PROMPT} prompt + "
          f"{NEW_TOKENS} new tokens", flush=True)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, PROMPT).astype(np.int32)
               for _ in range(SLOTS)]
    params = init_model(jax.random.PRNGKey(seed + 1), cfg)
    clean = replicate_params(params, N_ONE)
    del params
    for k in (0, SPEC_K):
        spec = AggSpec(f=F, gar="bulyan-krum", speculative_k=k)
        mode = f"speculative_k={k}" if k else "per-token"
        ref = serve_run(clean, cfg, spec, prompts)
        poisoned = poison_replicas(clean, F, "signflip", scale=10.0)
        got = serve_run(poisoned, cfg, spec, prompts)
        del poisoned
        print(f"[serve/{mode}] clean wall_s={ref['wall_s']:.2f} (cold "
              f"compiles included) poisoned wall_s={got['wall_s']:.2f} "
              f"(compile cache warm); aggregations={len(got['logits'])}",
              flush=True)
        check(all(len(t) == NEW_TOKENS for t in got["tokens"].values()),
              f"{mode}: every request got its {NEW_TOKENS} tokens")
        check(len(got["logits"]) == len(ref["logits"]),
              f"{mode}: same number of aggregations")
        worst = max(float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
                    for a, b in zip(got["logits"], ref["logits"]))
        check(worst <= LOGIT_RTOL,
              f"{mode}: aggregated logits equal the clean ensemble's "
              f"(worst {worst:.3e} of max |logit| <= {LOGIT_RTOL})")
        check(all(float(np.max(s[..., -1])) == 0.0 for s in got["selected"]),
              f"{mode}: poisoned replica never selected")
        print(f"  peak_bytes_in_use={peak_bytes(dev)}", flush=True)
    del clean


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def mesh_phase(cfg, seed: int, devices) -> None:
    """The step on a (2, 2) data x model mesh, with the xla and the
    pallas distance backends, each compared with the xla step on one
    device.  One reference serves both: on one v5e the pallas step
    reproduces the xla step bitwise, and a pallas one-device program
    would be the slowest compile of the call.

    Both sides run their matmuls in full f32.  At the default precision
    the MXU rounds f32 operands to bf16, and a sharded and a one-device
    program need not round the same operands: on a v5e their first AdamW
    updates then differed on 6.3e-3 of the parameters, which hides what
    this compares, the sharding.  The process runs nothing else."""
    jax.config.update("jax_default_matmul_precision", "highest")
    mesh = make_host_mesh((2, 2))
    print(f"== mesh: (2, 2) data x model over {len(devices)} chips, "
          f"n={N_MESH} f={F} bulyan-krum, omniscient_linf {dict(ATTACK)}, "
          f"AdamW, 1x{SEQ} tokens per worker", flush=True)
    opt = optimizer()
    params = init_model(jax.random.PRNGKey(seed), cfg)
    state0 = jax.device_get((params, opt.init(params)))
    del params
    batch = train_batch(cfg, N_MESH, seed, 0)
    p_sh = param_shardings(state0[0], mesh)
    o_sh = param_shardings(state0[1], mesh)
    b_sh = {k: NamedSharding(mesh, batch_pspec(v.shape, mesh,
                                               worker_axis=True))
            for k, v in batch.items()}
    one_dev = SingleDeviceSharding(devices[0])
    jobs = {"mesh/xla/one-device": (
        jax.jit(make_train_step(cfg, train_spec("xla"), opt),
                donate_argnums=(0, 1)),
        tuple(shapes(t, one_dev) for t in (*state0, batch)), None)}
    for backend in ("xla", "pallas"):
        jobs[f"mesh/{backend}/sharded"] = (
            jax.jit(make_train_step(cfg, train_spec(backend), opt, mesh=mesh),
                    donate_argnums=(0, 1), out_shardings=(p_sh, o_sh, None)),
            (shapes(state0[0], p_sh), shapes(state0[1], o_sh),
             shapes(batch, b_sh)), mesh)
    programs = compile_steps(jobs)
    check("tpu_custom_call" in programs["mesh/pallas/sharded"].as_text(),
          "sharded pallas step runs its Pallas kernel compiled "
          "(tpu_custom_call in the program)")
    params, state = jax.device_put(state0, devices[0])
    params, state, m = programs.pop("mesh/xla/one-device")(
        params, state, batch)
    ref = jax.device_get((params, m))
    del params, state
    check_metrics(ref[1], 0)
    for backend in ("xla", "pallas"):
        params = jax.device_put(state0[0], p_sh)
        state = jax.device_put(state0[1], o_sh)
        params, state, m = programs.pop(f"mesh/{backend}/sharded")(
            params, state, jax.device_put(batch, b_sh))
        got = jax.device_get((params, m))
        del params, state
        check_metrics(got[1], 0)
        print("  peak_bytes_in_use per chip: "
              + " ".join(str(peak_bytes(d)) for d in devices), flush=True)
        verdicts = [
            same_loss(f"{backend} sharded vs xla one-device", got[1], ref[1]),
            agreement(f"{backend} sharded vs xla one-device updated "
                      f"parameters", got[0], ref[0])]
        for verdict in verdicts:
            check(*verdict)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: train + serve on one chip; 4: only the "
                         "2x2 mesh train path and its one-device reference")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 1

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    cfg = llama_cut()
    abstract = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg))
    n_params = sum(int(np.prod(s.shape))
                   for s in jax.tree_util.tree_leaves(abstract))
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"jax {jax.__version__}", flush=True)
    print(f"cut: {cfg.name}: layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads} kv_heads={cfg.n_kv_heads} "
          f"head_dim={cfg.head_dim} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size} (1/{VOCAB_CUT} of published) "
          f"param_dtype={cfg.param_dtype}; params={n_params}", flush=True)

    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            mesh_phase(cfg, args.seed, devices[:4])
        else:
            train_phase(cfg, args.seed, dev)
            serve_phase(cfg, args.seed, dev)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"wall_s={time.perf_counter() - t0:.2f} "
          f"peak_bytes_in_use={peak_bytes(dev)}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
