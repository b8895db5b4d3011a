"""Distributed-path tests.

Semantics: ``distributed_aggregate`` (per-leaf, tensordot distances,
windowed coordinate phase) must equal the flat core GARs on the same data.

Mesh execution: an 8-device host-platform subprocess runs the sharded
train step on a (4, 2) mesh and checks it against the single-device result
— the subprocess is required because jax pins the device count at first
init and the rest of the suite must see 1 CPU device.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import get_gar
from repro.core import pytree as pt
from repro.dist.robust import (coordinate_phase_nd, distributed_aggregate,
                               inject_byzantine, pairwise_sq_dists_tree)

KEY = jax.random.PRNGKey(4)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _stacked_tree(n, key=KEY):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"a": {"w": jax.random.normal(k1, (n, 8, 16))},
            "b": jax.random.normal(k2, (n, 64)),
            "c": jax.random.normal(k3, (n, 2, 3, 4))}


class TestDistributedAggregateSemantics:
    def test_pairwise_dists_match_flat(self):
        tree = _stacked_tree(9)
        flat, _ = pt.stack_flatten(tree)
        from repro.core import pairwise_sq_dists
        np.testing.assert_allclose(pairwise_sq_dists_tree(tree),
                                   pairwise_sq_dists(flat),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("gar", ["average", "cwmed", "trimmed_mean",
                                     "krum", "geomed", "multikrum",
                                     "brute", "centered_clip",
                                     "bulyan-krum", "bulyan-geomed"])
    def test_matches_core_gar(self, gar):
        n, f = 11, 2
        tree = _stacked_tree(n)
        agg, _ = distributed_aggregate(tree, f, gar)
        flat, ctx = pt.stack_flatten(tree)
        want = pt.unflatten(get_gar(gar)(flat, f).gradient, ctx)
        for a, w in zip(jax.tree_util.tree_leaves(agg),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(a, w, rtol=1e-4, atol=1e-5)

    def test_coordinate_phase_nd_matches_flat(self):
        from repro.core import coordinate_phase
        sel = jax.random.normal(KEY, (9, 4, 5, 6))
        out = coordinate_phase_nd(sel, 2)
        want = coordinate_phase(sel.reshape(9, -1), 2).reshape(4, 5, 6)
        np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)

    def test_inject_byzantine_replaces_last_f(self):
        n, f = 11, 3
        tree = _stacked_tree(n)
        out = inject_byzantine(tree, f, "signflip")
        # structure must be preserved exactly: same top-level names, same
        # per-leaf shapes and dtypes
        assert isinstance(out, dict) and set(out) == set(tree)
        for name in ("a", "b", "c"):
            for a, o in zip(jax.tree_util.tree_leaves(tree[name]),
                            jax.tree_util.tree_leaves(out[name])):
                assert a.shape == o.shape
                assert a.dtype == o.dtype
        la = jax.tree_util.tree_leaves(tree)
        lo = jax.tree_util.tree_leaves(out)
        for a, o in zip(la, lo):
            np.testing.assert_array_equal(a[:n - f], o[:n - f])
            mean = np.mean(np.asarray(a[:n - f]), axis=0)
            np.testing.assert_allclose(o[n - f], -mean, rtol=1e-4,
                                       atol=1e-5)


_SUBPROCESS_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_reduced
    from repro.dist.mesh import make_host_mesh
    from repro.dist.sharding import param_shardings, batch_pspec
    from repro.agg import AggSpec
    from repro.dist.train import make_train_step
    from repro.models import init_model
    from repro.optim import get_optimizer

    assert jax.device_count() == 8
    cfg = get_reduced("llama3_2_3b")
    mesh = make_host_mesh((4, 2))
    key = jax.random.PRNGKey(0)
    params = init_model(key, cfg)
    opt = get_optimizer("momentum", 1e-2)
    spec = AggSpec(f=0, gar="bulyan-krum", attack="none")
    # n=4 workers < 4f+3 for f>0; use f=0 quorum-free bulyan? bulyan needs
    # n>=3 for f=0; theta=n, beta=n -> plain trimmed behaviour.
    step = make_train_step(cfg, spec, opt)
    n, b, s = 4, 2, 32
    batch = {
        "tokens": jax.random.randint(key, (n, b, s), 0, cfg.vocab_size),
        "labels": jax.random.randint(key, (n, b, s), 0, cfg.vocab_size),
    }
    # single-device reference
    ref_params, ref_state, ref_m = jax.jit(step)(params, opt.init(params),
                                                 batch)

    with jax.set_mesh(mesh):
        psh = param_shardings(params, mesh)
        sp = jax.device_put(params, psh)
        so = jax.device_put(opt.init(params), param_shardings(
            opt.init(params), mesh))
        bsh = jax.tree_util.tree_map(
            lambda x: jax.device_put(x, NamedSharding(mesh, batch_pspec(
                x.shape, mesh, worker_axis=True))), batch)
        out_params, out_state, m = jax.jit(step)(sp, so, bsh)

    diffs = [float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                   - b.astype(jnp.float32))))
             for a, b in zip(jax.tree_util.tree_leaves(ref_params),
                             jax.tree_util.tree_leaves(out_params))]
    print(json.dumps({
        "max_diff": max(diffs),
        "loss_diff": abs(float(ref_m["loss"]) - float(m["loss"])),
        "devices": jax.device_count(),
    }))
""")


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", _SUBPROCESS_SCRIPT],
                       capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["devices"] == 8
    assert out["max_diff"] < 5e-2   # fp reassociation across shardings
    assert out["loss_diff"] < 1e-3
