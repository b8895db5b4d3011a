"""Pallas kernel validation: shape/dtype sweeps against the pure-jnp
oracles, in interpret mode (CPU container; kernels target TPU)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import bulyan_select, coord_stats, pairwise_gram, ref
from repro.kernels.ops import bulyan_coordinate, pairwise_distances

KEY = jax.random.PRNGKey(7)
SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "src")


@pytest.mark.parametrize("n,d", [(5, 64), (7, 100), (9, 129), (16, 2048),
                                 (25, 333), (31, 4096)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pairwise_gram_sweep(n, d, dtype):
    g = (jax.random.normal(KEY, (n, d)) * 3.0).astype(dtype)
    out = pairwise_gram(g, block_d=512, interpret=True)
    want = ref.pairwise_gram_ref(g)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(out, want, rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("theta,f", [(5, 0), (7, 1), (9, 2), (11, 2),
                                     (13, 3), (16, 3), (31, 7)])
@pytest.mark.parametrize("d", [100, 129, 1024])
def test_bulyan_select_sweep(theta, f, d):
    s = jax.random.normal(jax.random.fold_in(KEY, theta * d), (theta, d))
    out = bulyan_select(s, f, block_d=256, interpret=True)
    want = ref.bulyan_select_ref(s, f)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bulyan_select_dtypes(dtype):
    """bf16 quantization makes distance *ties* likely; when two
    beta-windows are equidistant from the median, any minimal-deviation
    window is a valid Bulyan output (the paper's arg min is a set).  The
    oracle check therefore accepts every tie-optimal window mean."""
    theta, f, d = 9, 2, 512
    beta = theta - 2 * f
    s = jax.random.normal(KEY, (theta, d)).astype(dtype)
    out = np.asarray(bulyan_select(s, f, interpret=True), np.float32)

    sv = np.sort(np.asarray(s, np.float32), axis=0)
    med = sv[(theta - 1) // 2]
    ok = np.zeros((d,), bool)
    best = np.full((d,), np.inf)
    means = []
    for w in range(theta - beta + 1):
        dev = np.abs(sv[w:w + beta] - med).sum(0)
        means.append(sv[w:w + beta].mean(0))
        best = np.minimum(best, dev)
    eps = 1e-5 if dtype == jnp.float32 else 1e-2
    for w in range(theta - beta + 1):
        dev = np.abs(sv[w:w + beta] - med).sum(0)
        tie_ok = dev <= best * (1 + eps) + eps
        close = np.abs(out - means[w]) <= 1e-2 + 1e-3 * np.abs(means[w])
        ok |= tie_ok & close
    assert ok.all(), f"{(~ok).sum()} coords not a tie-optimal window mean"


def test_block_size_invariance():
    s = jax.random.normal(KEY, (11, 1000))
    outs = [bulyan_select(s, 2, block_d=b, interpret=True)
            for b in (128, 256, 1024)]
    for o in outs[1:]:
        np.testing.assert_allclose(outs[0], o, rtol=1e-6)


@pytest.mark.parametrize("theta,f", [(3, 0), (5, 0), (3, 1), (5, 1),
                                     (7, 2), (9, 3)])
def test_bulyan_select_equals_coordinate_phase_bitwise(theta, f):
    """The kernel and the XLA coordinate phase run one body (the sorting
    network and window of ``repro.core.bulyan``), so they agree bitwise,
    padded tail tile included; with f = 0 both are the in-order mean of
    the sorted values."""
    from repro.core.bulyan import coordinate_phase
    s = jax.random.normal(jax.random.fold_in(KEY, theta), (theta, 300))
    out = bulyan_select(s, f, block_d=128, interpret=True)
    want = jax.jit(coordinate_phase, static_argnums=1)(s, f)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_common_reexports_core_sort_network():
    """The kernels' shared primitives still import from
    ``repro.kernels.common``; the sort network and window are
    ``repro.core.bulyan``'s own objects, defined once."""
    from repro.core import bulyan as core_bulyan
    from repro.kernels.common import (bulyan_window, coord_median,
                                      coord_trimmed_mean, oe_sort_rows,
                                      resolve_interpret)
    assert oe_sort_rows is core_bulyan.oe_sort_rows
    assert bulyan_window is core_bulyan.bulyan_window
    assert callable(coord_median) and callable(coord_trimmed_mean)
    assert resolve_interpret(True) is True


def test_ops_wrappers_dispatch():
    g = jax.random.normal(KEY, (9, 300))
    np.testing.assert_allclose(
        pairwise_distances(g, use_pallas=True, block_d=128),
        pairwise_distances(g, use_pallas=False), rtol=1e-4, atol=1e-4)
    s = jax.random.normal(KEY, (9, 300))
    np.testing.assert_allclose(
        bulyan_coordinate(s, 2, use_pallas=True, block_d=128),
        bulyan_coordinate(s, 2, use_pallas=False), rtol=1e-5, atol=1e-5)


def test_import_initialises_no_backend():
    """Importing the kernels (and the runtime above them) must not ask for
    the backend: on a chip that would claim the device as an import side
    effect.  The wrappers resolve it when called."""
    code = ("import repro.kernels, repro.dist, repro.serving\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n")
    env = dict(os.environ, PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr[-3000:]


def test_gram_padding_exact():
    """Zero-padding d must not change distances."""
    g = jax.random.normal(KEY, (6, 130))  # forces padding at block 128
    out = pairwise_gram(g, block_d=128, interpret=True)
    want = ref.pairwise_gram_ref(g)
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n,f,d", [(7, 1, 200), (9, 2, 1000), (16, 3, 513),
                                   (15, 0, 128)])
def test_coord_stats_sweep(n, f, d):
    g = jax.random.normal(jax.random.fold_in(KEY, n * d), (n, d)) * 2.0
    med, trim = coord_stats(g, f, block_d=256, interpret=True)
    rmed, rtrim = ref.coord_stats_ref(g, f)
    np.testing.assert_allclose(med, rmed, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(trim, rtrim, rtol=1e-5, atol=1e-6)
