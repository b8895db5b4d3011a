"""Unit tests for the GAR core (paper §2.3 + §4)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (REGISTRY, coordinate_phase, coordinate_phase_ref,
                        get_gar, krum, pairwise_sq_dists, quorum,
                        select_indices)

KEY = jax.random.PRNGKey(0)


def _grads(n, d, key=KEY, scale=1.0):
    return scale * jax.random.normal(key, (n, d)) + 1.0


class TestPairwiseDists:
    def test_matches_naive(self):
        g = _grads(9, 64)
        d2 = pairwise_sq_dists(g)
        naive = np.array([[np.sum((g[i] - g[j]) ** 2) for j in range(9)]
                          for i in range(9)])
        np.testing.assert_allclose(d2, naive, rtol=1e-4, atol=1e-4)

    def test_zero_diagonal(self):
        d2 = pairwise_sq_dists(_grads(5, 16))
        np.testing.assert_allclose(np.diag(d2), 0.0, atol=1e-6)


class TestQuorums:
    @pytest.mark.parametrize("name,f,n_bad", [
        ("krum", 2, 6), ("brute", 2, 4), ("trimmed_mean", 3, 6)])
    def test_too_few_workers_raise(self, name, f, n_bad):
        with pytest.raises(ValueError):
            get_gar(name)(_grads(n_bad, 8), f)

    def test_bulyan_quorum(self):
        with pytest.raises(ValueError):
            get_gar("bulyan-krum")(_grads(8, 8), 2)  # needs 11
        assert quorum("bulyan-krum", 2) == 11
        assert quorum("krum", 2) == 7


class TestKrum:
    def test_selects_clump_member(self):
        # 8 clumped honest + 2 far outliers: krum must pick a clumped one
        g = jnp.concatenate([_grads(8, 32, scale=0.1),
                             100.0 + _grads(2, 32, jax.random.PRNGKey(1))])
        res = krum(g, 2)
        assert float(res.selected[-2:].sum()) == 0.0

    def test_score_formula(self):
        g = _grads(7, 16)
        f = 1
        res = krum(g, f)
        d2 = np.array(pairwise_sq_dists(g))  # writable copy
        np.fill_diagonal(d2, np.inf)
        k = 7 - f - 2
        scores = np.sort(d2, axis=1)[:, :k].sum(1)
        np.testing.assert_allclose(res.scores, scores, rtol=1e-4)
        assert int(np.argmin(scores)) == int(np.argmax(res.selected))


class TestGeoMed:
    def test_is_a_proposed_vector(self):
        g = _grads(9, 32)
        res = get_gar("geomed")(g, 2)
        dists = np.min(np.linalg.norm(np.asarray(g) -
                                      np.asarray(res.gradient), axis=1))
        assert dists < 1e-5


class TestBrute:
    def test_excludes_outliers(self):
        g = jnp.concatenate([_grads(5, 16, scale=0.1),
                             50.0 + _grads(2, 16, jax.random.PRNGKey(2))])
        res = get_gar("brute")(g, 2)
        assert float(res.selected[-2:].sum()) == 0.0
        # output = mean of the clumped 5
        np.testing.assert_allclose(res.gradient, jnp.mean(g[:5], axis=0),
                                   rtol=1e-4, atol=1e-4)


class TestCoordinateWise:
    def test_cwmed_is_median(self):
        g = _grads(7, 32)
        res = get_gar("cwmed")(g, 2)
        np.testing.assert_allclose(res.gradient, np.median(g, axis=0),
                                   rtol=1e-5)

    def test_trimmed_mean_removes_extremes(self):
        g = jnp.concatenate([_grads(7, 8, scale=0.1),
                             1e6 * jnp.ones((2, 8))])
        res = get_gar("trimmed_mean")(g, 2)
        assert float(jnp.max(jnp.abs(res.gradient))) < 10.0


class TestBulyan:
    def test_selection_count_and_uniqueness(self):
        g = _grads(11, 64)
        idx = select_indices(g, 2, base="krum")
        assert idx.shape == (7,)  # theta = 11 - 4
        assert len(set(np.asarray(idx).tolist())) == 7

    def test_coordinate_phase_windowed_equals_ref(self):
        for theta, f in [(7, 1), (9, 2), (13, 3), (5, 0)]:
            sel = jax.random.normal(jax.random.PRNGKey(theta), (theta, 512))
            np.testing.assert_allclose(coordinate_phase(sel, f),
                                       coordinate_phase_ref(sel, f),
                                       rtol=1e-5, atol=1e-6)

    def test_output_bracketed_by_selected_values(self):
        # Prop 2 mechanism: each output coordinate lies within the range of
        # the selected workers' values at that coordinate
        g = _grads(11, 128)
        f = 2
        res = get_gar("bulyan-krum")(g, f)
        idx = select_indices(g, f, base="krum")
        sel = np.asarray(g[idx])
        assert np.all(res.gradient >= sel.min(0) - 1e-5)
        assert np.all(res.gradient <= sel.max(0) + 1e-5)

    @pytest.mark.parametrize("base", ["krum", "geomed", "average", "brute"])
    def test_bases_run(self, base):
        g = _grads(7, 32)
        res = get_gar(f"bulyan-{base}")(g, 1)
        assert res.gradient.shape == (32,)
        assert bool(jnp.all(jnp.isfinite(res.gradient)))


def _legacy_coordinate_phase(selected, f):
    """The coordinate phase as it was before the sorting network: an XLA
    sort, cumulative sums and a ``take_along_axis`` of the best window
    (kept here as a second oracle)."""
    theta = selected.shape[0]
    beta = theta - 2 * f
    s = jnp.sort(selected, axis=0)
    med = s[(theta - 1) // 2]
    if beta == theta:
        return jnp.mean(s, axis=0)
    zeros = jnp.zeros_like(s[:1])
    cd = jnp.concatenate([zeros, jnp.cumsum(jnp.abs(s - med[None]), axis=0)])
    cv = jnp.concatenate([zeros, jnp.cumsum(s, axis=0)])
    n_win = theta - beta + 1
    w = jnp.argmin(cd[beta:] - cd[:n_win], axis=0)
    best = jnp.take_along_axis(cv[beta:] - cv[:n_win], w[None], axis=0)[0]
    return best / beta


def _tied_stack(kind, theta, shape):
    """A (theta, *shape) stack; ``kind`` plants ties the sort must keep."""
    x = jax.random.normal(jax.random.PRNGKey(theta), (theta,) + shape)
    if kind == "duplicate_rows":
        x = x.at[1].set(x[0]).at[theta - 1].set(x[2])
    elif kind == "constant_coordinate":
        x = x.at[:, 0].set(0.5)
    elif kind == "signed_zeros":
        x = x.at[0].set(0.0).at[theta - 1].set(-0.0)
        x = x.at[:, 0].set(jnp.where(jnp.arange(theta) % 2, 0.0, -0.0)
                           .reshape((theta,) + (1,) * (len(shape) - 1)))
    return x


@pytest.mark.parametrize("kind", ["random", "duplicate_rows",
                                  "constant_coordinate", "signed_zeros"])
@pytest.mark.parametrize("shape", [(256,), (4, 8, 16)])
@pytest.mark.parametrize("theta,f", [(3, 1), (5, 1), (7, 2), (9, 3),
                                     (21, 9)])
def test_coordinate_phase_matches_oracles(theta, f, shape, kind):
    sel = _tied_stack(kind, theta, shape)
    got = jax.jit(coordinate_phase, static_argnums=1)(sel, f)
    assert got.shape == shape
    np.testing.assert_allclose(got, coordinate_phase_ref(sel, f),
                               rtol=1e-5, atol=1e-6)
    legacy = jax.jit(_legacy_coordinate_phase, static_argnums=1)(sel, f)
    np.testing.assert_array_max_ulp(np.asarray(got), np.asarray(legacy),
                                    maxulp=1)


@pytest.mark.parametrize("theta,f", [(5, 1), (21, 9)])
def test_coordinate_phase_lowers_without_gather_sort_or_cumsum(theta, f):
    """The coordinate phase is elementwise work along the worker axis:
    no gather, no sort and no cumulative sum (``reduce_window``)."""
    sel = jax.ShapeDtypeStruct((theta, 64, 33), jnp.float32)
    text = jax.jit(coordinate_phase, static_argnums=1).lower(sel, f).as_text()
    for op in ("stablehlo.gather", "stablehlo.sort", "reduce_window"):
        assert op not in text, op


class TestNoByzantineBehaviour:
    @pytest.mark.parametrize("name", ["krum", "geomed", "cwmed",
                                      "trimmed_mean", "bulyan-krum",
                                      "multikrum", "centered_clip"])
    def test_close_to_mean_without_adversary(self, name):
        g = _grads(15, 256, scale=0.05)
        res = get_gar(name)(g, 3)
        dev = float(jnp.linalg.norm(res.gradient - jnp.mean(g, axis=0)))
        assert dev < 1.0  # honest spread is tiny; any sane GAR is close
