"""Tracing hooks (``repro.obs.trace``) in the train step and the serving
engine.

  1. **scopes** — every op of the train step (synchronous or
     asynchronous) that reads the step's inputs sits under a program
     scope (``train/<phase>`` or ``agg``), and every
     aggregation op of the compiled step under exactly one of
     ``agg/gram``, ``agg/select`` and ``agg/coordinate``;
  2. **host spans** — under ``jax.profiler`` a tiny ensemble engine's
     trace holds ``serve/admit`` with its request id and its four phases
     inside it, and ``serve/step`` with its three;
  3. **counters** — ``count_compiles`` counts a fresh ``jax.jit`` and
     nothing for a repeated decode step, and leaves no listener behind;
     the engine's counters count admissions, decode steps and the wait
     in its queue.
"""
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import monitoring
from jax.extend import core as jcore

from repro.agg import AggSpec
from repro.configs import get_reduced
from repro.dist.serve_robust import replicate_params
from repro.dist.async_train import init_async_state, make_async_train_step
from repro.dist.train import make_train_step
from repro.models import init_model
from repro.obs import count_compiles, host_span
from repro.optim import get_optimizer
from repro.serving import Request, ServingEngine

KEY = jax.random.PRNGKey(0)
#: the program's scopes: the train step's phases and the aggregation
PROGRAM_SCOPE = re.compile(
    r"(^|/)(train/(grads|inject|bus|optimizer|diagnostics)|agg)(/|$)")
PHASES = ("gram", "select", "coordinate")


# ---------------------------------------------------------------------------
# 1. scopes of the train step
# ---------------------------------------------------------------------------

def _train_step(gar, asynchronous=False):
    cfg = get_reduced("qwen1_5_4b")
    opt = get_optimizer("adamw", 3e-4, weight_decay=0.01)
    params = init_model(KEY, cfg)
    n = 7
    batch = {"tokens": jnp.zeros((n, 1, 16), jnp.int32),
             "labels": jnp.ones((n, 1, 16), jnp.int32)}
    spec = AggSpec(f=1, gar=gar, attack="omniscient_linf",
                   attack_kwargs=(("margin", 3.0),))
    args = (params, opt.init(params), batch)
    if asynchronous:
        return (make_async_train_step(cfg, spec, opt),
                args + (init_async_state(spec, params, n),))
    return make_train_step(cfg, spec, opt), args


@pytest.mark.parametrize("gar,asynchronous", [
    pytest.param(gar, asynchronous, id=gar + ("-async" if asynchronous
                                              else ""))
    for asynchronous in (False, True)
    for gar in ("bulyan-krum", "krum", "multikrum", "trimmed_mean")])
def test_every_train_step_op_carries_a_program_scope(gar, asynchronous):
    """Ops that depend on the step's inputs carry a program scope, in the
    synchronous step and in the asynchronous one (its bus stage under
    ``train/bus``).  The rest are constants (rope tables, masks) that
    JAX hoists out of the layer scan with a name stack of their own."""
    step, args = _train_step(gar, asynchronous)
    jaxpr = jax.make_jaxpr(step)(*args).jaxpr
    live = set(map(id, jaxpr.invars))
    unscoped = []
    for eqn in jaxpr.eqns:
        if not any(not isinstance(v, jcore.Literal) and id(v) in live
                   for v in eqn.invars):
            continue
        live.update(map(id, eqn.outvars))
        name = str(eqn.source_info.name_stack)
        if not PROGRAM_SCOPE.search(name):
            unscoped.append((eqn.primitive.name, name))
    assert not unscoped, unscoped[:10]


@pytest.mark.parametrize("gar", ["bulyan-krum", "krum", "multikrum",
                                 "trimmed_mean"])
def test_every_aggregation_op_sits_in_one_phase(gar):
    step, args = _train_step(gar)
    text = jax.jit(step).lower(*args).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    agg = [n for n in names if "agg" in n.split("/")]
    assert agg

    def phases(name):
        parts = name.split("/")
        return [p for p in parts[parts.index("agg") + 1:] if p in PHASES]

    wrong = sorted(n for n in agg if len(phases(n)) != 1)
    assert not wrong, wrong[:10]
    # the train step's own phases hold no aggregation op
    assert not [n for n in agg if "train/" in n]


# ---------------------------------------------------------------------------
# 2. host spans of the serving engine
# ---------------------------------------------------------------------------

def _engine(n_slots=2):
    cfg = get_reduced("llama3_2_3b")
    stacked = replicate_params(init_model(KEY, cfg), 5, jitter=1e-3,
                               key=KEY)
    return ServingEngine(stacked, cfg, n_slots=n_slots, cache_len=32,
                         ensemble=AggSpec(f=1, gar="krum"))


def _prompt(length=5):
    return np.arange(1, length + 1, dtype=np.int32)


def _host_events(logdir):
    """``[(name, start_ns, end_ns, stats)]`` of the trace's host spans."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve/"):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


def test_engine_spans_nest_in_the_profiler_trace(tmp_path):
    eng = _engine()
    eng.run([Request(rid=0, prompt=_prompt(), max_new_tokens=2)])  # warm
    logdir = str(tmp_path / "trace")
    with jax.profiler.trace(logdir):
        eng.submit(Request(rid=41, prompt=_prompt(), max_new_tokens=3))
        for _ in range(3):
            eng.step()
    events = _host_events(logdir)

    def inside(parent, name):
        _, s, e, stats = parent
        return [ev for ev in events if ev[0] == name and s <= ev[1]
                and ev[2] <= e and ev[3].get("rid") == stats.get("rid")]

    admits = [ev for ev in events if ev[0] == "serve/admit"]
    assert len(admits) == 1 and admits[0][3]["rid"] == 41
    for phase in ("prefill", "first_token", "splice", "reset"):
        assert len(inside(admits[0], f"serve/admit/{phase}")) == 1, phase
    steps = [ev for ev in events if ev[0] == "serve/step"]
    assert len(steps) == 2       # the third step finds nothing to decode
    for step in steps:
        for phase in ("decode", "sample", "emit"):
            _, s, e, _ = step
            assert [ev for ev in events if ev[0] == f"serve/step/{phase}"
                    and s <= ev[1] and ev[2] <= e], phase


def test_host_span_outside_a_profiler_is_a_plain_context():
    with host_span("serve/admit", rid=3):
        value = 1
    assert value == 1


# ---------------------------------------------------------------------------
# 3. compile counts and the engine's counters
# ---------------------------------------------------------------------------

def _listeners():
    return len(monitoring.get_event_time_span_listeners())


def test_count_compiles_counts_a_fresh_jit():
    before = _listeners()
    counters = {"compiles": 0, "compile_s": 0.0}
    x = jnp.arange(4.0)
    with count_compiles(counters):
        jax.jit(lambda v: jnp.sin(v) * 3.0)(x).block_until_ready()
    assert counters["compiles"] >= 1
    assert counters["compile_s"] > 0.0
    assert _listeners() == before


def test_count_compiles_removes_its_listener_on_error():
    before = _listeners()
    counters = {"compiles": 0, "compile_s": 0.0}
    with pytest.raises(RuntimeError):
        with count_compiles(counters):
            raise RuntimeError("inside")
    assert _listeners() == before
    assert counters == {"compiles": 0, "compile_s": 0.0}


def test_repeated_decode_step_compiles_nothing():
    eng = _engine(n_slots=1)
    eng.submit(Request(rid=0, prompt=_prompt(), max_new_tokens=6))
    eng.step()                           # admits, compiles the decode step
    eng.step()
    before = _listeners()
    compiles = eng.counters["compiles"]
    eng.step()
    eng.step()
    assert eng.counters["compiles"] == compiles
    assert _listeners() == before


def test_engine_counters_count_admissions_steps_and_queueing():
    eng = _engine()
    direct = Request(rid=0, prompt=_prompt(), max_new_tokens=3)
    assert eng.admit(direct)
    assert eng.counters["queue_s"] == 0.0        # never queued
    eng.submit(Request(rid=1, prompt=_prompt(), max_new_tokens=3))
    eng.step()
    eng.step()
    c = eng.telemetry()["counters"]
    assert c["admissions"] == 2
    assert c["decode_steps"] == 2
    assert c["queue_s"] > 0.0
    assert c["compiles"] >= 1 and c["compile_s"] > 0.0
    # a copy: the engine keeps counting on its own dict
    c["admissions"] = -1
    assert eng.counters["admissions"] == 2
