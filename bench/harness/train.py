"""Driver of ``"kind": "train"`` traffic: the robust train step.

Set-up builds one object, the compiled ``make_train_step`` with its
parameters and optimizer state, and drives it from the seed through its
first three steps on the window's own call and feed.  Those steps are
what the reference checks: each step's loss, the first aggregate's norm
per leaf (read back from the first AdamW moment, ``m = (1 - b1) g``),
and each leaf's change after three steps.  The same object then runs the
window: a fixed number of steps, sized in set-up to last ``--seconds``,
with one wait at the end.  With ``--trace 1`` a few more seconds of
steps run under the profiler.  After the window the program's memory is
freed and the reference runs the same three steps in float32 at the
highest matmul precision.

The traffic file gives the committee (``workers``, ``f``, ``gar``,
``attack``, ``attack_margin``), the sequences per worker and their
length, the token stream's parameters, the size of the feed's pool of
distinct batches, the optimizer and the traced seconds.
"""
from __future__ import annotations

import gc
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import common, weights
from harness import trace as tr

#: steps the reference follows and the numbers compare
CHECK_STEPS = 3


# ---------------------------------------------------------------------------
# traffic: the seeded token stream
# ---------------------------------------------------------------------------

def _transition_table(vocab: int, seed: int, branch: int) -> np.ndarray:
    rng = np.random.default_rng((seed, 13))
    return rng.integers(0, vocab, size=(vocab, branch)).astype(np.int32)


def lm_stream(table: np.ndarray, seqs: int, length: int, stream: int,
              seed: int, noise_p: float) -> tuple:
    """``(tokens, labels)`` of ``seqs`` sequences from one stream of a
    Markov chain whose tokens each have ``branch`` likely successors,
    with a share ``noise_p`` of uniform tokens (labels are next tokens)."""
    vocab, branch = table.shape
    rng = np.random.default_rng((seed, stream, 3))
    toks = np.empty((seqs, length + 1), np.int64)
    toks[:, 0] = rng.integers(0, vocab, size=seqs)
    choice = rng.integers(0, branch, size=(seqs, length))
    noise = rng.random((seqs, length)) < noise_p
    rand = rng.integers(0, vocab, size=(seqs, length))
    for t in range(length):
        nxt = table[toks[:, t], choice[:, t]]
        toks[:, t + 1] = np.where(noise[:, t], rand[:, t], nxt)
    return toks[:, :-1].astype(np.int32), toks[:, 1:].astype(np.int32)


def batches(mix: dict, vocab: int, seed: int, count: int) -> list:
    """``count`` batches ``{"tokens", "labels"}`` of ``(workers,
    sequences, length)``; every row of every batch is its own stream."""
    st = mix["stream"]
    table = _transition_table(vocab, seed, st["branch"])
    n = mix["workers"]
    out = []
    for b in range(count):
        rows = [lm_stream(table, mix["sequences_per_worker"],
                          mix["tokens_per_sequence"], b * n + w, seed,
                          st["noise_p"]) for w in range(n)]
        out.append({"tokens": np.stack([r[0] for r in rows]),
                    "labels": np.stack([r[1] for r in rows])})
    return out


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------

class Cell:
    """One train cell's program, compiled once for any number of seeds."""

    def __init__(self, cfg: dict, mix: dict, step_factory=None):
        from repro.agg import AggSpec
        from repro.dist import train as dist_train
        from repro.models import init_model
        from repro.optim import get_optimizer

        common.apply_precision(cfg)
        self.cfg, self.mix = cfg, mix
        self.c = cfg["config"]
        self.ref = common.module("reference", cfg["reference"])
        self.mcfg = common.module("families",
                                  cfg["model_type"]).program_config(cfg)
        o = mix["optimizer"]
        self.opt_hp = o
        self.opt = get_optimizer(o["name"], o["lr"],
                                 weight_decay=o["weight_decay"], b1=o["b1"],
                                 b2=o["b2"], eps=o["eps"])
        self.spec = AggSpec(f=mix["f"], gar=mix["gar"], attack=mix["attack"],
                            attack_kwargs=(("margin", mix["attack_margin"]),))
        abstract = jax.eval_shape(
            lambda: init_model(jax.random.PRNGKey(0), self.mcfg))
        self._check_layout(abstract)
        factory = step_factory or dist_train.make_train_step
        self.step = jax.jit(factory(self.mcfg, self.spec, self.opt),
                            donate_argnums=(0, 1))
        self.tokens_per_step = (mix["workers"] * mix["sequences_per_worker"]
                                * mix["tokens_per_sequence"])
        self.compiled = None
        self.compile_s = None

    def _check_layout(self, abstract) -> None:
        """The benchmark's parameter layout is the program's."""
        ours = {p: tuple(s) for p, s in self.ref.param_shapes(self.c).items()}
        theirs = {"/".join(str(k.key) for k in path): tuple(x.shape)
                  for path, x in jax.tree_util.tree_flatten_with_path(
                      abstract)[0]}
        if ours != theirs:
            raise ValueError(f"parameter layout differs from the program's: "
                             f"{sorted(set(ours.items()) ^ set(theirs.items()))}")

    def params(self, seed: int):
        p = weights.make_params(self.ref, self.c, seed,
                                jnp.dtype(self.cfg["precision"]["param_dtype"]))
        p["tail"] = {}
        return p

    def feed(self, seed: int) -> list:
        vocab = self.mcfg.vocab_size
        return [jax.device_put(b) for b in
                batches(self.mix, vocab, seed, self.mix["pool"])]

    def compile(self, params, state, batch) -> None:
        t0 = time.perf_counter()
        self.compiled = self.step.lower(params, state, batch).compile()
        self.compile_s = time.perf_counter() - t0

    def check_steps(self, seed: int, pool: list) -> tuple:
        """Build the state from ``seed``, run the first steps; returns
        ``(params, state, readings)`` for the window to continue from."""
        from reference import committee

        params = self.params(seed)
        state = jax.jit(self.opt.init)(params)
        if self.compiled is None:
            self.compile(params, state, pool[0])
        losses = []
        for t in range(CHECK_STEPS):
            params, state, m = self.compiled(params, state, pool[t])
            losses.append(m["loss"])
            if t == 0:
                b1 = self.opt_hp["b1"]
                agg = {k: v / (1.0 - b1) for k, v in
                       committee.leaf_norms(strip_tail(state["m"])).items()}
        change = committee.change_norms(strip_tail(params),
                                        strip_tail(self.params(seed)))
        readings = {"loss": [float(x) for x in losses], "agg_norms": agg,
                    "change_norms": change}
        return params, state, readings


def strip_tail(tree):
    return {k: v for k, v in tree.items() if k != "tail"}


# ---------------------------------------------------------------------------
# the reference and the comparison
# ---------------------------------------------------------------------------

def reference_readings(cell: Cell, seed: int, pool_host: list,
                       dtype=jnp.float32, half_batch: bool = False) -> dict:
    """The plain reference's readings of the same steps (the control:
    ``dtype=bfloat16``; a planted fault: ``half_batch``)."""
    from reference import committee

    with jax.default_matmul_precision("highest"):
        p0 = strip_tail(cell.params(seed))
        out = committee.run_steps(
            cell.ref, cell.c, p0, pool_host[:CHECK_STEPS], f=cell.mix["f"],
            margin=cell.mix["attack_margin"], opt=cell.opt_hp, dtype=dtype,
            half_batch=half_batch)
        p3 = out.pop("params")
        out["change_norms"] = committee.change_norms(
            p3, strip_tail(cell.params(seed)))
    return out


def compare(got: dict, ref: dict) -> dict:
    """The numbers compared: the widest relative loss gap over the steps,
    and the worst leaf's gap between the two norms of the first aggregate
    and of the change after the steps, each against the larger of the
    reference's norm of that leaf and of the median leaf.  Leaves whose
    reference aggregate is under a thousandth of the median leaf's move
    by round-off alone and are left out."""
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(got["loss"],
                                                       ref["loss"]))
    med = float(np.median(list(ref["agg_norms"].values())))
    kept = [k for k, v in ref["agg_norms"].items() if v >= 1e-3 * med]

    def worst(key):
        scale = float(np.median([ref[key][k] for k in kept]))
        return max(abs(got[key][k] - ref[key][k]) / max(ref[key][k], scale)
                   for k in kept)

    return {"loss_gap": loss_gap, "agg_norm_gap": worst("agg_norms"),
            "update_norm_gap": worst("change_norms"),
            "leaves_left_out": sorted(set(ref["agg_norms"]) - set(kept))}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run(name: str, cfg: dict, mix: dict, limits: dict, seed: int,
        seconds: float, trace: bool, devices, t_start: float,
        step_factory=None) -> tuple:
    """One run of a train cell; returns ``(result, checks, e2e, numbers
    for the per-layer metrics, breakdown, extra)``."""
    common.one_chip(name, devices)
    cell = Cell(cfg, mix, step_factory)
    pool = cell.feed(seed)
    pool_host = [jax.device_get(b) for b in pool[:CHECK_STEPS]]
    params, state, got = cell.check_steps(seed, pool)

    # size the window: one timed step, then as many as fill it
    t0 = time.perf_counter()
    params, state, m = cell.compiled(params, state, pool[CHECK_STEPS])
    jax.block_until_ready((params, state, m))
    steps = max(1, round(seconds / (time.perf_counter() - t0)))
    k0 = CHECK_STEPS + 1

    t_window = time.perf_counter()
    setup_s = t_window - t_start
    for i in range(steps):
        params, state, m = cell.compiled(params, state,
                                         pool[(k0 + i) % len(pool)])
    jax.block_until_ready((params, state, m))
    window_s = time.perf_counter() - t_window
    last_loss = float(m["loss"])
    tokens_per_s = steps * cell.tokens_per_step / window_s
    e2e = {"train_tokens_per_s": (tokens_per_s, "tokens/s"),
           "setup_s": (setup_s, "s")}

    per_layer = {}
    breakdown = None
    if trace:
        per_layer, breakdown = _traced(cell, params, state, pool,
                                       k0 + steps, tokens_per_s, name)
    peak = common.peak_bytes(devices)
    del params, state, m, pool
    gc.collect()

    ref = reference_readings(cell, seed, pool_host)
    nums = compare(got, ref)
    checks = {k: {"value": nums[k], "limit": limits[k]}
              for k in ("loss_gap", "agg_norm_gap", "update_norm_gap")}
    correct = (all(c["value"] <= c["limit"] for c in checks.values())
               and math.isfinite(last_loss))
    result = {"correct": bool(correct), "attempted": steps,
              "failed": 0 if math.isfinite(last_loss) else steps,
              "metrics": {}, "device": common.device_info(devices)}
    result["device"]["memory_peak_bytes"] = peak
    extra = {"compile_s": cell.compile_s,
             "memory_analysis": common.memory_analysis(cell.compiled),
             "steps": steps,
             "window_s": window_s, "leaves_left_out":
             nums["leaves_left_out"], "program_loss": got["loss"],
             "reference_loss": ref["loss"]}
    return result, checks, e2e, per_layer, breakdown, extra


def _traced(cell, params, state, pool, k0, tokens_per_s, name):
    """A few seconds of steps under the profiler, reduced to the cell's
    per-layer metrics."""
    secs = cell.mix["trace_seconds"]
    logdir = str(common.OUT_DIR / "trace" / name)
    per_step = cell.tokens_per_step / tokens_per_s
    steps = max(2, round(secs / per_step))
    tr.start(logdir)
    with jax.profiler.TraceAnnotation("bench/window"):
        with jax.profiler.TraceAnnotation("bench/dispatch"):
            for i in range(steps):
                params, state, m = cell.compiled(
                    params, state, pool[(k0 + i) % len(pool)])
        with jax.profiler.TraceAnnotation("bench/wait"):
            jax.block_until_ready((params, state, m))
    red = tr.reduce(tr.stop_and_load(logdir, "bench/window",
                                     tr.hlo_scopes(cell.compiled.as_text())))
    red["steps"] = steps
    return red, tr.breakdown(red)
