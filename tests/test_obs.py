"""Aggregation forensics & telemetry (repro.obs).

Pins the observability layer's four contracts:

  1. **bitwise identity** — ``obs-<base>`` returns the base rule's
     result unchanged on both the dense and the tree path, for every
     rule family (telemetry never touches the data path);
  2. **carrier composability** — the ``MetricsBuffer`` ring pushes
     under jit, composes with ``jax.eval_shape``, survives a
     numpy checkpoint roundtrip, and drains in chronological order
     across wraparound;
  3. **no host traffic** — the compiled telemetry train step lowers
     without host callbacks;
  4. **detection** — the drained forensics reproduce the paper's
     attack signatures (selection-entropy collapse under the
     omniscient attack, Byzantine rows ranked most suspect under a
     defended one) and the shared metrics schema holds across paths.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.agg import AggSpec, init_state, resolve_rule, rule_names
from repro.agg.fused import fused_name
from repro.dist.robust import distributed_aggregate
from repro.obs import (METRIC_SCHEMA, AggDiagnostics, MetricsBuffer,
                       drain, init_metrics_buffer, obs_name, push_record,
                       selection_collapsed, selection_entropy,
                       suspicion_scores)
from repro.obs.detect import margin_trajectory

KEY = jax.random.PRNGKey(7)

# one representative per rule family (base, bulyan-, buffered-, stale-,
# reputation-, fused- — obs- itself is the wrapper under test)
FAMILIES = sorted(set(rule_names()) | {
    "bulyan-krum", "buffered-cwmed", "stale-krum",
    "reputation-krum", "fused-krum"})


def _stack(name: str, f: int = 2, d: int = 48):
    rule = resolve_rule(name)
    n = max(rule.min_n(f), f + 3)
    return jax.random.normal(KEY, (n, d), jnp.float32), n


# ---------------------------------------------------------------------------
# 1. bitwise identity per family
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", FAMILIES)
def test_obs_dense_bitwise_identical(name):
    base = resolve_rule(name)
    obs = resolve_rule(obs_name(name))
    g, n = _stack(name)
    f = 2
    if base.stateful:
        bstate = init_state(base, g)
        ostate = init_state(obs, g)
        bres, bstate = base.dense_fn(g, f, bstate)
        ores, ostate = obs.dense_fn(g, f, ostate)
        # the base's own carried fields evolve identically
        for fld in base.state_fields:
            np.testing.assert_array_equal(
                np.concatenate([np.ravel(x) for x in
                                jax.tree_util.tree_leaves(
                                    getattr(bstate, fld))] or [np.zeros(0)]),
                np.concatenate([np.ravel(x) for x in
                                jax.tree_util.tree_leaves(
                                    getattr(ostate, fld))] or [np.zeros(0)]))
    else:
        bres = base.dense_fn(g, f)
        ores, ostate = obs.dense_fn(g, f, init_state(obs, g))
    np.testing.assert_array_equal(np.asarray(bres.gradient),
                                  np.asarray(ores.gradient))
    np.testing.assert_array_equal(np.asarray(bres.selected),
                                  np.asarray(ores.selected))
    np.testing.assert_array_equal(np.asarray(bres.scores),
                                  np.asarray(ores.scores))
    assert int(np.asarray(ostate.obs.cursor)) == 1


@pytest.mark.parametrize("name", [n for n in FAMILIES
                                  if resolve_rule(n).tree_fn is not None])
def test_obs_tree_bitwise_identical(name):
    f = 2
    rule = resolve_rule(name)
    n = max(rule.min_n(f), f + 3)
    k1, k2 = jax.random.split(KEY)
    tree = {"w": jax.random.normal(k1, (n, 6, 5)),
            "b": jax.random.normal(k2, (n, 7))}
    out_b = distributed_aggregate(tree, f, name)
    out_o = distributed_aggregate(tree, f, obs_name(name))
    for lb, lo in zip(jax.tree_util.tree_leaves(out_b[0]),
                      jax.tree_util.tree_leaves(out_o[0])):
        np.testing.assert_array_equal(np.asarray(lb), np.asarray(lo))
    np.testing.assert_array_equal(np.asarray(out_b[1].selected),
                                  np.asarray(out_o[1].selected))


# ---------------------------------------------------------------------------
# 2. MetricsBuffer carrier semantics
# ---------------------------------------------------------------------------

def _record(step: int, n: int) -> AggDiagnostics:
    v = jnp.full((n,), float(step), jnp.float32)
    return AggDiagnostics(step=jnp.float32(step), selected=v, scores=v,
                          dist_to_agg=v, trimmed_frac=v, reputation=v,
                          staleness=v, agg_dev=jnp.float32(step),
                          spread=jnp.float32(step))


def test_ring_wraparound_drains_chronologically():
    buf = init_metrics_buffer(4, 3)

    @jax.jit
    def push(b, s):
        return push_record(b, _record(0, 3)._replace(
            step=s.astype(jnp.float32)))

    for s in range(6):
        buf = push(buf, jnp.int32(s))
    out = drain(buf)
    assert out["pushed"] == 6
    assert [int(r["step"]) for r in out["records"]] == [2, 3, 4, 5]
    assert out["selection_frequency"].shape == (3,)


def test_buffer_composes_with_eval_shape():
    def body():
        buf = init_metrics_buffer(8, 5)
        return push_record(buf, _record(1, 5))

    abstract = jax.eval_shape(body)
    assert isinstance(abstract, MetricsBuffer)
    assert abstract.records.selected.shape == (8, 5)
    assert abstract.cursor.shape == ()


def test_buffer_checkpoint_roundtrip():
    buf = init_metrics_buffer(4, 3)
    for s in range(3):
        buf = push_record(buf, _record(s, 3))
    # checkpoint: leaves to host numpy, restore via tree_unflatten
    leaves, treedef = jax.tree_util.tree_flatten(buf)
    saved = [np.asarray(x) for x in leaves]
    restored = jax.tree_util.tree_unflatten(
        treedef, [jnp.asarray(x) for x in saved])
    a, b = drain(buf), drain(restored)
    assert a["pushed"] == b["pushed"]
    for ra, rb in zip(a["records"], b["records"]):
        for k in ra:
            np.testing.assert_array_equal(ra[k], rb[k])
    # and the restored ring keeps recording
    more = drain(push_record(restored, _record(9, 3)))
    assert more["pushed"] == 4


def test_drain_of_empty_obs_is_empty():
    out = drain(())
    assert out["pushed"] == 0 and out["records"] == []


# ---------------------------------------------------------------------------
# registry & spec plumbing
# ---------------------------------------------------------------------------

def test_obs_wraps_outermost_and_rejects_nesting():
    assert resolve_rule("obs-krum").name == "obs-krum"
    assert resolve_rule("obs-stale-krum").stateful
    assert fused_name("obs-krum") == "obs-fused-krum"
    assert resolve_rule("obs-fused-krum").name == "obs-fused-krum"
    with pytest.raises(KeyError, match="cannot nest"):
        resolve_rule("obs-obs-krum")
    with pytest.raises(KeyError, match="unknown GAR"):
        resolve_rule("obs-nonsense")


def test_spec_telemetry_selects_effective_gar():
    assert AggSpec(f=2, gar="krum").effective_gar == "krum"
    assert AggSpec(f=2, gar="krum", telemetry=True).effective_gar \
        == "obs-krum"
    spec = AggSpec(f=2, gar="krum", telemetry=True)
    assert spec.rule().name == "obs-krum"
    # quorum contract is the base's own
    assert spec.rule().min_n(2) == resolve_rule("krum").min_n(2)


# ---------------------------------------------------------------------------
# 3. compiled step stays host-callback-free
# ---------------------------------------------------------------------------

def test_no_host_callbacks_in_compiled_telemetry_step():
    from repro.data import ByzantineBatcher
    from repro.models import simple
    from repro.optim import get_optimizer
    from repro.training.trainer import (init_flat_agg_state,
                                        make_byzantine_step)

    def loss_fn(params, x, y):
        return simple.classification_loss(
            simple.mnist_mlp_forward(params, x), y, params)

    spec = AggSpec(n_workers=9, f=2, gar="krum", attack="signflip",
                   telemetry=True)
    opt = get_optimizer("sgd", 0.05)
    params = simple.init_mnist_mlp(KEY)
    x, y = ByzantineBatcher("mnist", spec.n_honest, 8).batch(0)
    step = make_byzantine_step(loss_fn, opt, spec, attack_on=True)
    txt = jax.jit(step).lower(
        params, opt.init(params), jnp.asarray(x), jnp.asarray(y), KEY,
        init_flat_agg_state(spec, params)).as_text()
    assert "callback" not in txt.lower()


# ---------------------------------------------------------------------------
# 4. detection regressions (the paper's attack, observed live)
# ---------------------------------------------------------------------------

def _run_trainer(gar, attack, n_workers, f, steps):
    from repro.data import ByzantineBatcher
    from repro.models import simple
    from repro.optim import get_optimizer
    from repro.training import ByzantineTrainer

    def loss_fn(params, x, y):
        return simple.classification_loss(
            simple.mnist_mlp_forward(params, x), y, params)

    kw = (("gar_name", gar),) if attack == "omniscient_lp" else ()
    spec = AggSpec(n_workers=n_workers, f=f, gar=gar, attack=attack,
                   attack_kwargs=kw, telemetry=True)
    tr = ByzantineTrainer(loss_fn, simple.init_mnist_mlp(KEY),
                          get_optimizer("sgd", 0.05), spec, seed=3)
    tr.run(ByzantineBatcher("mnist", spec.n_honest, 16), steps)
    return tr


def test_telemetry_off_run_is_bitwise_identical():
    """The flip side of the obs contract at trainer level: a telemetry
    run updates params exactly like an uninstrumented one."""
    runs = {}
    for telemetry in (False, True):
        from repro.data import ByzantineBatcher
        from repro.models import simple
        from repro.optim import get_optimizer
        from repro.training import ByzantineTrainer

        def loss_fn(params, x, y):
            return simple.classification_loss(
                simple.mnist_mlp_forward(params, x), y, params)

        spec = AggSpec(n_workers=9, f=2, gar="krum",
                       attack="signflip", telemetry=telemetry)
        tr = ByzantineTrainer(loss_fn, simple.init_mnist_mlp(KEY),
                              get_optimizer("sgd", 0.05), spec, seed=3)
        tr.run(ByzantineBatcher("mnist", spec.n_honest, 16), 3)
        runs[telemetry] = tr
    for a, b in zip(jax.tree_util.tree_leaves(runs[False].params),
                    jax.tree_util.tree_leaves(runs[True].params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for ma, mb in zip(runs[False].history, runs[True].history):
        assert ma == mb
    assert runs[False].telemetry()["pushed"] == 0
    assert runs[True].telemetry()["pushed"] == 3


def test_suspicion_ranks_byzantine_rows_first():
    tr = _run_trainer("krum", "signflip", n_workers=9, f=2, steps=5)
    out = tr.telemetry()
    s = suspicion_scores(out["records"], out["selection_frequency"])
    assert s.shape == (9,)
    # the defended attack's rows (the appended tail) rank most suspect
    assert set(np.argsort(s)[-2:]) == {7, 8}


def test_selection_entropy_collapses_under_paper_attack():
    clean = _run_trainer("krum", "none", n_workers=9, f=0, steps=5)
    poisoned = _run_trainer("krum", "omniscient_lp", n_workers=9, f=2,
                            steps=5)
    h_clean = selection_entropy(clean.telemetry()["selection_frequency"])
    h_att = selection_entropy(poisoned.telemetry()["selection_frequency"])
    assert h_att < h_clean
    assert selection_collapsed(
        poisoned.telemetry()["selection_frequency"])
    # margins exist for every recorded step and stay plottable
    m = margin_trajectory(poisoned.telemetry()["records"])
    assert m.shape == (5,) and np.all(m >= -1.0)


# ---------------------------------------------------------------------------
# satellite: one metrics schema across execution paths
# ---------------------------------------------------------------------------

def test_metric_keys_consistent_across_flat_paths():
    from repro.data import ByzantineBatcher
    from repro.models import simple
    from repro.optim import get_optimizer
    from repro.training import AsyncByzantineTrainer, ByzantineTrainer

    def loss_fn(params, x, y):
        return simple.classification_loss(
            simple.mnist_mlp_forward(params, x), y, params)

    sync_spec = AggSpec(n_workers=9, f=2, gar="krum",
                        attack="signflip")
    sync = ByzantineTrainer(loss_fn, simple.init_mnist_mlp(KEY),
                            get_optimizer("sgd", 0.05), sync_spec)
    sync.run(ByzantineBatcher("mnist", sync_spec.n_honest, 8), 1)
    sync_keys = set(sync.history[0]) - {"step"}

    async_spec = AggSpec(n_workers=9, f=2, gar="krum",
                         attack="signflip", async_tau=2)
    a = AsyncByzantineTrainer(loss_fn, simple.init_mnist_mlp(KEY),
                              get_optimizer("sgd", 0.05), async_spec)
    a.run(ByzantineBatcher("mnist", async_spec.n_honest, 8), 1)
    async_keys = set(a.history[0]) - {"step"}

    assert sync_keys <= set(METRIC_SCHEMA)
    assert async_keys <= set(METRIC_SCHEMA)
    # the async path emits exactly the sync keys plus the async extras —
    # the historic drift (staleness_excess missing on the flat async
    # path) cannot reappear
    extras = {k for k, (paths, _) in METRIC_SCHEMA.items()
              if paths == "async"}
    assert async_keys == sync_keys | extras
    assert "staleness_excess" in async_keys
