"""Driver of ``"kind": "serve"`` traffic: robust ensemble decoding.

``ServingEngine`` in ensemble mode over a stack of replicas, the last
``f`` poisoned (the honest weights sign-flipped and scaled), is offered
an open loop of requests at the mix's fixed rate: each request is
submitted when it is due, whether or not earlier ones have finished.
Sizes and gaps between arrivals are drawn at fixed quantiles of the
mix's distributions and shuffled by the seed, so every seed offers the
same set of sizes and gaps in another order; prompts are rounded up to
the mix's buckets, whose shapes set-up warms.  Times are taken where a
client sees them, after each ``ServingEngine.step`` returns, and a
request's latency counts from when it was due.

After the window, a sample of the finished requests drawn from the seed,
with the one that got most tokens among them, goes to the reference:
each replica's whole-sequence forward pass over the prompt and the
served tokens, aggregated position by position.  The number compared is
the widest gap by which a served token's aggregated logit lies below
the reference's best at its position.
"""
from __future__ import annotations

import gc
import json
import math
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import common, weights
from harness import trace as tr


# ---------------------------------------------------------------------------
# traffic: sizes and gaps at fixed quantiles, order and tokens by the seed
# ---------------------------------------------------------------------------

def _lognormal(count: int, median: float, sigma: float) -> np.ndarray:
    nd = statistics.NormalDist()
    return np.array([median * math.exp(sigma * nd.inv_cdf((i + 0.5) / count))
                     for i in range(count)])


def requests(mix: dict, vocab: int, seed: int) -> list:
    """The offered requests in arrival order: ``(due seconds after the
    window opens, prompt tokens, output length)``."""
    k = mix["requests"]
    p, o = mix["prompt"], mix["output"]
    buckets = np.array(p["buckets"])
    raw = _lognormal(k, p["median"], p["sigma"])
    prompts = buckets[np.minimum(np.searchsorted(buckets, raw),
                                 len(buckets) - 1)]
    outs = np.clip(np.round(_lognormal(k, o["median"], o["sigma"])),
                   o["min"], o["max"]).astype(int)
    gaps = np.array([-math.log(1.0 - (i + 0.5) / k) for i in range(k)])
    gaps /= mix["arrivals_per_s"]                   # exponential quantiles
    rng = np.random.default_rng((seed, 5))
    prompts, outs, gaps = (rng.permutation(x) for x in (prompts, outs, gaps))
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    return [(float(due[i]), rng.integers(0, vocab, size=int(prompts[i]))
             .astype(np.int32), int(outs[i])) for i in range(k)]


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------

class Cell:
    """One serve cell's program and weights."""

    def __init__(self, cfg: dict, mix: dict):
        from repro.agg import AggSpec

        common.apply_precision(cfg)
        self.cfg, self.mix = cfg, mix
        self.c = cfg["config"]
        self.ref = common.module("reference", cfg["reference"])
        self.mcfg = common.module("families",
                                  cfg["model_type"]).program_config(cfg)
        self.spec = AggSpec(f=mix["f"], gar=mix["gar"])
        self.dtype = jnp.dtype(cfg["precision"]["param_dtype"])

    def params(self, seed: int):
        p = weights.make_params(self.ref, self.c, seed, self.dtype,
                                stack=self.mix["replicas"],
                                poison_scale=self.mix["poison_scale"])
        p["tail"] = {}
        return p

    def engine(self, params):
        from repro.serving import ServingEngine

        return ServingEngine(params, self.mcfg, n_slots=self.mix["slots"],
                             cache_len=self.mix["cache_len"],
                             ensemble=self.spec)


def warm_up(engine, mix: dict) -> None:
    """One request per prompt bucket through admission and decoding."""
    from repro.serving import Request

    engine.run([Request(rid=-1 - i, prompt=np.zeros(b, np.int32),
                        max_new_tokens=2)
                for i, b in enumerate(mix["prompt"]["buckets"])])
    jax.block_until_ready(engine.cache)


class Loop:
    """The open loop over one engine, with per-token times as the client
    sees them."""

    def __init__(self, engine, offered: list):
        from repro.serving import Request

        self.Request, self.engine, self.offered = Request, engine, offered
        self.t0 = None
        self.next = 0                        # next offered request
        self.live = {}                       # rid -> Request
        self.due = {}                        # rid -> due time
        self.times = {}                      # rid -> token times
        self.done = {}                       # rid -> (prompt, tokens)
        self.admit_s = []
        admit = engine.admit

        def timed_admit(req):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench/admit"):
                ok = admit(req)
            if ok:
                self.admit_s.append(time.perf_counter() - t0)
            return ok

        engine.admit = timed_admit

    def run(self, seconds: float) -> tuple:
        """Serve until ``seconds`` after the loop first opened; returns
        ``(window start, window end, rids due in this call)``."""
        if self.t0 is None:
            self.t0 = time.perf_counter()
        start, first = time.perf_counter(), self.next
        while True:
            now = time.perf_counter()
            if now - self.t0 >= seconds:
                break
            while (self.next < len(self.offered)
                   and self.offered[self.next][0] <= now - self.t0):
                due, prompt, out = self.offered[self.next]
                req = self.Request(rid=self.next, prompt=prompt,
                                   max_new_tokens=out)
                self.live[self.next] = req
                self.due[self.next] = self.t0 + due
                self.times[self.next] = []
                self.engine.submit(req)
                self.next += 1
            if self.live:
                self._step()
            elif self.next < len(self.offered):
                time.sleep(max(0.0, min(
                    self.offered[self.next][0] - (now - self.t0),
                    seconds - (now - self.t0))))
            else:
                break
        return start, time.perf_counter(), list(range(first, self.next))

    def _step(self) -> None:
        with jax.profiler.TraceAnnotation("bench/engine.step"):
            self.engine.step()
        now = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench/client"):
            for rid, req in list(self.live.items()):
                got = len(req.generated or ())
                self.times[rid].extend([now] * (got - len(self.times[rid])))
                if req.done:
                    del self.live[rid]
                    self.done[rid] = (req.prompt, list(req.generated))


# ---------------------------------------------------------------------------
# the reference and the comparison
# ---------------------------------------------------------------------------

def sample(done: dict, seed: int, count: int) -> list:
    """``count`` finished requests drawn from the seed, with the one that
    got most tokens among them."""
    rids = sorted(done)
    longest = max(rids, key=lambda r: len(done[r][1]))
    rest = [r for r in rids if r != longest]
    rng = np.random.default_rng((seed, 7))
    pick = (rng.choice(rest, size=min(count - 1, len(rest)), replace=False)
            if rest else [])
    return [longest] + [int(r) for r in pick]


def reference_gaps(cell: Cell, seed: int, served: list,
                   control_dtype=None) -> list:
    """Per request, the widest gap of the served tokens under the
    reference; with ``control_dtype``, the widest gap of the tokens that
    the reference computed in that type puts first instead.  Sequences
    are padded to the cache length (the forward pass is causal), so one
    program serves every request."""
    from reference import ensemble

    mix = cell.mix
    f, n, length = mix["f"], mix["replicas"], mix["cache_len"]
    out = []
    with jax.default_matmul_precision("highest"):
        base = weights.make_params(cell.ref, cell.c, seed, jnp.float32)
        fwd = jax.jit(lambda p, t: cell.ref.logits(p, cell.c, t))
        scale = jax.jit(lambda p, s: jax.tree_util.tree_map(
            lambda x: (s * x.astype(jnp.float32)).astype(x.dtype), p))
        low = (jax.jit(lambda p: jax.tree_util.tree_map(
            lambda x: x.astype(control_dtype), p)) if control_dtype
            else None)

        def agg(params, seq):
            h = fwd(params, seq)
            p = fwd(scale(params, -mix["poison_scale"]), seq)
            return ensemble.aggregate(jnp.stack([h] * (n - f) + [p] * f), f)

        for prompt, toks in served:
            seq = np.zeros(length, np.int32)
            body = np.concatenate([prompt, toks[:-1]])
            seq[:len(body)] = body
            seq = jnp.asarray(seq)
            at = slice(len(prompt) - 1, len(prompt) - 1 + len(toks))
            ref = np.asarray(agg(base, seq))[at]
            if low is None:
                out.append(ensemble.served_gap(ref, toks))
            else:
                ctl = np.asarray(agg(low(base), seq))[at]
                out.append(ensemble.served_gap(ref, ctl.argmax(axis=-1)))
    return out


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def _p95(xs: list):
    return float(np.percentile(np.asarray(xs, np.float64), 95)) if xs \
        else None


def _window_numbers(loop: Loop, rids: list, end: float, cfg: dict,
                    mix: dict) -> dict:
    """Tokens seen by ``end``; the time to first token of every request
    due in the window (censored at ``end`` where none came); every gap
    between a request's consecutive tokens; the model FLOPs of every
    replica's prefill and decode tokens seen by ``end``."""
    work = common.module("metrics", "_work")
    tokens, ttft, gaps, flops = 0, [], [], 0.0
    for r in rids:
        ts = [t for t in loop.times[r] if t <= end]
        plen = len(loop.offered[r][1])
        tokens += len(ts)
        ttft.append((ts[0] if ts else end) - loop.due[r])
        gaps.extend(b - a for a, b in zip(ts, ts[1:]))
        for k in range(len(ts)):
            flops += mix["replicas"] * (
                work.forward_flops(cfg, plen) if k == 0
                else work.decode_flops(cfg, plen + k))
    return {"tokens": tokens, "ttft": ttft, "gaps": gaps, "flops": flops}


def run(name: str, cfg: dict, mix: dict, limits: dict, seed: int,
        seconds: float, trace: bool, devices, t_start: float,
        hook=None) -> tuple:
    """One run of a serve cell; returns ``(result, checks, e2e, numbers
    for the per-layer metrics, breakdown, extra)``."""
    common.one_chip(name, devices)
    cell = Cell(cfg, mix)
    vocab = cell.mcfg.vocab_size
    engine = cell.engine(cell.params(seed))
    decode = engine._decode
    if hook is not None:
        hook(engine)
    warm_up(engine, mix)
    loop = Loop(engine, requests(mix, vocab, seed))

    setup_s = time.perf_counter() - t_start
    t0, t1, rids = loop.run(seconds)
    w = _window_numbers(loop, rids, t1, cfg, mix)
    admits = list(loop.admit_s)
    red, breakdown = {}, None
    if trace:
        logdir = str(common.OUT_DIR / "trace" / name)
        tr.start(logdir)
        with jax.profiler.TraceAnnotation("bench/window"):
            loop.run(seconds + mix["trace_seconds"])
        red = tr.reduce(tr.stop_and_load(logdir, "bench/window"))
        breakdown = tr.breakdown(red)
    red.update({"admit_ms": 1000 * float(np.mean(admits)) if admits
                else None, "model_flops": w["flops"],
                "window_s_e2e": t1 - t0, "ttft_p95_s": _p95(w["ttft"]),
                "itl_p95_s": _p95(w["gaps"])})
    e2e = {"serve_tokens_per_s": (w["tokens"] / (t1 - t0), "tokens/s"),
           "setup_s": (setup_s, "s")}
    peak = common.peak_bytes(devices)
    decode_memory = common.memory_analysis(decode.lower(
        engine.params, engine.cache, jnp.asarray(engine.last_token)[:, None],
        jnp.asarray(engine.positions), engine.agg_state).compile())
    done = dict(loop.done)
    del engine, loop
    gc.collect()

    served = ([done[r] for r in sample(done, seed, mix["check_sample"])]
              if done else [])
    # a window in which no request finished served nothing to check
    gap = max(reference_gaps(cell, seed, served), default=math.inf)
    checks = {"logit_gap": {"value": gap, "limit": limits["logit_gap"]}}
    result = {"correct": bool(gap <= limits["logit_gap"]),
              "attempted": len(rids), "failed": 0, "metrics": {},
              "device": common.device_info(devices)}
    result["device"]["memory_peak_bytes"] = peak
    extra = {"decode_memory_analysis": decode_memory,
             "requests_due": len(rids), "requests_finished": len(done),
             "admissions": len(admits), "window_s": t1 - t0,
             "served_tokens_checked": sum(len(t) for _, t in served)}
    return result, checks, e2e, red, breakdown, extra


def calibrate(w: dict, cfg: dict, mix: dict, args) -> tuple:
    """Readings for ``bench/calibrate.py``: per seed, the program's widest
    gap after a window of ``args.seconds``, and the control's on the same
    prompts and served tokens."""
    cell = Cell(cfg, mix)
    vocab = cell.mcfg.vocab_size
    out = {"program": [], "control": []}
    for seed in sorted(set(args.seeds) | set(args.control_seeds)):
        engine = cell.engine(cell.params(seed))
        warm_up(engine, mix)
        loop = Loop(engine, requests(mix, vocab, seed))
        loop.run(args.seconds)
        done = dict(loop.done)
        del engine, loop
        gc.collect()
        served = [done[r] for r in sample(done, seed, mix["check_sample"])]
        for kind, dtype, seeds in (("program", None, args.seeds),
                                   ("control", jnp.bfloat16,
                                    args.control_seeds)):
            if seed in seeds:
                gaps = reference_gaps(cell, seed, served, dtype)
                row = {"logit_gap": max(gaps), "per_request": gaps,
                       "tokens": sum(len(t) for _, t in served)}
                print(json.dumps({"kind": kind, "seed": seed, **row}),
                      flush=True)
                out[kind].append(row)
    return ("logit_gap",), out


def sweep(cfg: dict, mix: dict, args) -> tuple:
    """Tokens per second completed and latencies at each offered rate of
    ``args.rates``, one fresh engine and window of ``args.seconds`` each:
    where the completed rate stops following the offered one is the
    highest rate the cell sustains."""
    cell = Cell(cfg, mix)
    vocab = cell.mcfg.vocab_size
    out = {"sweep": []}
    seed = (args.seeds or [0])[0]
    for rate in (float(r) for r in args.rates.split(",")):
        engine = cell.engine(cell.params(seed))
        warm_up(engine, mix)
        loop = Loop(engine, requests(dict(mix, arrivals_per_s=rate), vocab,
                                     seed))
        t0, t1, rids = loop.run(args.seconds)
        w = _window_numbers(loop, rids, t1, cfg, mix)
        row = {"rate": rate, "tokens_per_s": w["tokens"] / (t1 - t0),
               "finished_per_s": len(loop.done) / (t1 - t0),
               "due": len(rids), "finished": len(loop.done),
               "admit_ms": 1000 * float(np.mean(loop.admit_s)),
               "ttft_p50_s": float(np.median(w["ttft"])),
               "ttft_p95_s": _p95(w["ttft"]), "itl_p95_s": _p95(w["gaps"])}
        print(json.dumps(row), flush=True)
        del engine, loop
        gc.collect()
    return (), out
