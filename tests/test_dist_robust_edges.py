"""Edge cases of the distributed robust-aggregation path that the main
semantics tests (test_dist.py) don't cover: quorum violations, the f=0
degenerate, single-leaf trees, mixed/bf16 dtypes, the coordinate-phase
window, and the injection's honest moments against a float64 form."""
import math
import statistics

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pytree as pt
from repro.dist.robust import (_delta_bar, _honest_moments,
                               coordinate_phase_nd, distributed_aggregate,
                               inject_byzantine, pairwise_sq_dists_tree)

KEY = jax.random.PRNGKey(11)


def _tree(n, dtype=jnp.float32, key=KEY):
    k1, k2 = jax.random.split(key)
    return {"w": jax.random.normal(k1, (n, 6, 4)).astype(dtype),
            "b": jax.random.normal(k2, (n, 5)).astype(dtype)}


class TestQuorum:
    def test_bulyan_quorum_raises(self):
        # f=1 needs n >= 4f+3 = 7
        with pytest.raises(ValueError, match="n >= 7"):
            distributed_aggregate(_tree(6), 1, "bulyan-krum")

    def test_krum_quorum_raises(self):
        # f=1 needs n >= 2f+3 = 5
        with pytest.raises(ValueError, match="n >= 5"):
            distributed_aggregate(_tree(4), 1, "krum")

    def test_unknown_gar_raises(self):
        with pytest.raises(KeyError, match="unknown GAR"):
            distributed_aggregate(_tree(7), 1, "no-such-rule")

    def test_non_distance_bulyan_base_rejected_early(self):
        # flat bulyan supports average/brute bases; the distributed
        # phase 1 works from distances alone and must say so up front
        with pytest.raises(KeyError, match="distance-only"):
            distributed_aggregate(_tree(7), 1, "bulyan-brute")

    def test_quorum_satisfied_at_boundary(self):
        agg, _ = distributed_aggregate(_tree(7), 1, "bulyan-krum")
        assert all(bool(jnp.all(jnp.isfinite(l)))
                   for l in jax.tree_util.tree_leaves(agg))


class TestDegenerateF0:
    def test_bulyan_f0_is_plain_mean(self):
        """f=0: theta=n, beta=theta, so selection keeps everyone and the
        coordinate phase averages all values — plain mean."""
        tree = _tree(5)
        agg, _ = distributed_aggregate(tree, 0, "bulyan-krum")
        want = jax.tree_util.tree_map(lambda l: jnp.mean(l, axis=0), tree)
        for a, w in zip(jax.tree_util.tree_leaves(agg),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(a, w, rtol=1e-5, atol=1e-6)

    def test_trimmed_mean_f0_is_average(self):
        tree = _tree(5)
        a0, _ = distributed_aggregate(tree, 0, "trimmed_mean")
        av, _ = distributed_aggregate(tree, 0, "average")
        for a, w in zip(jax.tree_util.tree_leaves(a0),
                        jax.tree_util.tree_leaves(av)):
            np.testing.assert_allclose(a, w, rtol=1e-5, atol=1e-6)

    def test_inject_f0_is_identity(self):
        tree = _tree(5)
        out = inject_byzantine(tree, 0, "signflip")
        for a, o in zip(jax.tree_util.tree_leaves(tree),
                        jax.tree_util.tree_leaves(out)):
            np.testing.assert_array_equal(a, o)


class TestSingleLeaf:
    @pytest.mark.parametrize("gar", ["krum", "geomed", "bulyan-krum",
                                     "cwmed"])
    def test_single_leaf_matches_flat(self, gar):
        n, f = 11, 2
        tree = {"only": jax.random.normal(KEY, (n, 33))}
        agg, _ = distributed_aggregate(tree, f, gar)
        flat, ctx = pt.stack_flatten(tree)
        from repro.core import get_gar
        want = pt.unflatten(get_gar(gar)(flat, f).gradient, ctx)
        np.testing.assert_allclose(agg["only"], want["only"],
                                   rtol=1e-4, atol=1e-5)

    def test_vector_leaf_dists(self):
        """Leaves with no trailing dims (one scalar per worker) hit the
        empty-axes tensordot (outer-product Gram)."""
        n = 7
        tree = {"s": jax.random.normal(KEY, (n,)),
                "m": jax.random.normal(jax.random.fold_in(KEY, 1), (n, 3))}
        flat, _ = pt.stack_flatten(tree)
        from repro.core import pairwise_sq_dists
        np.testing.assert_allclose(pairwise_sq_dists_tree(tree),
                                   pairwise_sq_dists(flat),
                                   rtol=1e-4, atol=1e-4)


class TestDtypes:
    @pytest.mark.parametrize("gar", ["krum", "cwmed", "bulyan-krum"])
    def test_bf16_grads_aggregated_in_fp32(self, gar):
        """bf16 leaves: accumulation runs fp32 (matching stack_flatten's
        cast in the flat reference) and the output returns in bf16."""
        n, f = 11, 2
        tree = _tree(n, dtype=jnp.bfloat16)
        agg, _ = distributed_aggregate(tree, f, gar)
        for leaf in jax.tree_util.tree_leaves(agg):
            assert leaf.dtype == jnp.bfloat16
        want, _ = pt.aggregate_pytree(tree, gar, f)
        for a, w in zip(jax.tree_util.tree_leaves(agg),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(a.astype(jnp.float32),
                                       w.astype(jnp.float32),
                                       rtol=2e-2, atol=2e-2)

    def test_mixed_dtype_tree(self):
        n, f = 9, 1
        tree = {"hi": jax.random.normal(KEY, (n, 8)),
                "lo": jax.random.normal(jax.random.fold_in(KEY, 2), (n, 8)
                                        ).astype(jnp.bfloat16)}
        agg, _ = distributed_aggregate(tree, f, "trimmed_mean")
        assert agg["hi"].dtype == jnp.float32
        assert agg["lo"].dtype == jnp.bfloat16

    def test_distance_matrix_fp32_from_bf16(self):
        tree = _tree(7, dtype=jnp.bfloat16)
        d2 = pairwise_sq_dists_tree(tree)
        assert d2.dtype == jnp.float32


class TestInjectParity:
    """The dist attacks must agree with the flat reference's conventions
    (core.attacks): global coordinate indexing, verbatim explicit gamma,
    and the flat defaults."""

    def test_lp_poisons_coordinate_in_later_leaf(self):
        n, f = 9, 2
        tree = {"a": jax.random.normal(KEY, (n, 4)),
                "b": jax.random.normal(jax.random.fold_in(KEY, 3), (n, 6))}
        # coord 7 lands in leaf "b" at local index 3
        out = inject_byzantine(tree, f, "omniscient_lp", coord=7,
                               gamma=5.0)
        mean_a = np.mean(np.asarray(tree["a"][:n - f]), axis=0)
        mean_b = np.mean(np.asarray(tree["b"][:n - f]), axis=0)
        np.testing.assert_allclose(out["a"][-1], mean_a, rtol=1e-5,
                                   atol=1e-6)
        want_b = mean_b.copy()
        want_b[3] += 5.0
        np.testing.assert_allclose(out["b"][-1], want_b, rtol=1e-5,
                                   atol=1e-6)

    def test_lp_explicit_gamma_ignores_margin(self):
        n, f = 9, 2
        tree = {"a": jax.random.normal(KEY, (n, 4))}
        out = inject_byzantine(tree, f, "omniscient_lp", coord=1,
                               gamma=3.0, margin=0.5)
        mean = np.mean(np.asarray(tree["a"][:n - f]), axis=0)
        np.testing.assert_allclose(float(out["a"][-1, 1] - mean[1]), 3.0,
                                   rtol=1e-5)

    def test_lp_coord_out_of_range_raises(self):
        tree = {"a": jax.random.normal(KEY, (9, 4))}
        with pytest.raises(ValueError, match="coord"):
            inject_byzantine(tree, 2, "omniscient_lp", coord=99)

    def test_lp_top_attacks_largest_mean_coordinate(self):
        n, f = 9, 2
        tree = {"a": jnp.ones((n, 3)) * 0.1,
                "b": jnp.ones((n, 4)).at[:, 2].set(50.0)}
        out = inject_byzantine(tree, f, "omniscient_lp", coord="top",
                               gamma=7.0)
        # largest-|mean| coordinate is b[2] (=50), attacked against its
        # sign: 50 - 7
        np.testing.assert_allclose(float(out["b"][-1, 2]), 43.0, rtol=1e-5)
        np.testing.assert_allclose(out["a"][-1],
                                   np.full((3,), 0.1, np.float32),
                                   rtol=1e-5)

    @pytest.mark.parametrize("attack", ["omniscient_lp", "omniscient_linf"])
    def test_gamma_closed_accepted(self, attack):
        """The flat API's gamma="closed" spelling must work (it is the
        only estimate the dist path has, so it aliases gamma=None)."""
        n, f = 9, 2
        tree = _tree(n)
        a = inject_byzantine(tree, f, attack, gamma="closed")
        b = inject_byzantine(tree, f, attack)
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b)):
            np.testing.assert_array_equal(x, y)

    def test_random_default_scale_matches_core(self):
        n, f = 20, 10
        tree = {"a": jnp.zeros((n, 2000))}
        out = inject_byzantine(tree, f, "random",
                               key=jax.random.PRNGKey(7))
        sd = float(np.std(np.asarray(out["a"][-f:])))
        assert 8.0 < sd < 12.0  # core.random_noise default scale=10.0


def _byzantine_f64(leaves, f, attack, margin, coord):
    """The attacks that read the honest moments, in plain float64 NumPy
    with ``inject_byzantine``'s defaults: one Byzantine row per leaf."""
    n_h = leaves[0].shape[0] - f
    honest = [np.asarray(l[:n_h], np.float64) for l in leaves]
    means = [h.mean(axis=0) for h in honest]
    d = sum(m.size for m in means)
    db = (2.0 / math.sqrt(math.pi)
          * sum(h.std(axis=0).sum() for h in honest) / d)
    if attack == "omniscient_linf":
        return [m + margin * db for m in means]
    if attack == "omniscient_lp":
        # the closed-form gamma against Krum, p = 2 (paper §B)
        g = math.sqrt(max((f + 1.0) / 2.0 - 0.5, 1e-9) * d) * db * margin
        out, off = [], 0
        for m in means:
            e = np.zeros(m.size)
            if 0 <= coord - off < m.size:
                e[coord - off] = 1.0
            out.append(m + g * e.reshape(m.shape))
            off += m.size
        return out
    if attack == "alie":
        n = n_h + f
        s = n // 2 + 1 - f
        z = statistics.NormalDist().inv_cdf(
            max(min((n - f - s) / (n - f), 1.0 - 1e-6), 1e-6))
        return [m - z * h.std(axis=0) for m, h in zip(means, honest)]
    if attack == "colluding_majority":
        norm = math.sqrt(sum((m * m).sum() for m in means)) + 1e-12
        return [m - 0.5 * db * m / norm for m in means]
    if attack == "slow_drift":
        return [m + 0.5 * db for m in means]
    assert attack == "signflip"
    return [-m for m in means]


_MOMENT_ATTACKS = {
    "omniscient_linf": {"margin": 3.0},
    "omniscient_lp": {"coord": 40, "margin": 0.5},
    "alie": {},
    "colluding_majority": {"direction": "anti"},
    "slow_drift": {},
    "signflip": {},
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("attack", sorted(_MOMENT_ATTACKS))
def test_injection_moments_match_float64(attack, dtype):
    """Every attack that reads the honest means or ``delta_bar`` takes
    them from one read of the honest rows.  The honest rows come back
    bit for bit, the Byzantine rows match a float64 form of the attack
    (bf16 rows: up to the cast's rounding), and a leaf whose honest rows
    all agree at a large value, where ``E[x^2] - E[x]^2`` would cancel
    below zero, gives ``delta_bar >= 0`` and finite rows."""
    n, f = 9, 2
    kw = _MOMENT_ATTACKS[attack]
    keys = jax.random.split(KEY, 3)
    tree = {"w": jax.random.normal(keys[0], (n, 6, 4)).astype(dtype),
            "b": (1.0 + jax.random.normal(keys[1], (n, 31))).astype(dtype),
            "s": jax.random.normal(keys[2], (n,)).astype(dtype)}
    run = jax.jit(lambda t: inject_byzantine(t, f, attack, **kw))
    out = run(tree)
    leaves = jax.tree_util.tree_leaves(tree)
    got = jax.tree_util.tree_leaves(out)
    want = _byzantine_f64(leaves, f, attack, kw.get("margin", 1.0),
                          kw.get("coord", 0))
    for l, g, w in zip(leaves, got, want):
        assert g.dtype == l.dtype and g.shape == l.shape
        np.testing.assert_array_equal(np.asarray(g[:n - f]),
                                      np.asarray(l[:n - f]))
        for row in np.asarray(g[n - f:].astype(jnp.float32), np.float64):
            # rtol 1e-5 about the float64 form (atol for the means that
            # cancel near zero); bf16 adds half an ulp of its rounding
            bound = 1e-5 * np.abs(w) + 1e-6
            if dtype == jnp.bfloat16:
                bound = bound + 2.0 ** -8 * np.abs(row)
            assert np.all(np.abs(row - w) <= bound), (
                np.max(np.abs(row - w) - bound))

    flat = {"c": jnp.full((n, 8, 16), 12345.679, dtype)}
    _, sums = _honest_moments(jax.tree_util.tree_leaves(flat), n - f)
    db = _delta_bar(sums, jax.tree_util.tree_leaves(flat))
    assert 0.0 <= float(sums[0]) and 0.0 <= float(db) < 1e-2
    assert bool(jnp.all(jnp.isfinite(
        run(flat)["c"].astype(jnp.float32))))


class TestCoordinatePhaseWindow:
    def test_windowed_matches_unwindowed(self):
        sel = jax.random.normal(KEY, (9, 7, 13))  # 91 coords
        full = coordinate_phase_nd(sel, 2)
        for window in (1, 8, 64, 91, 1000):
            win = coordinate_phase_nd(sel, 2, window=window)
            np.testing.assert_allclose(win, full, rtol=1e-6, atol=1e-7)

    def test_beta_lt_one_raises(self):
        sel = jax.random.normal(KEY, (4, 5))
        with pytest.raises(ValueError, match="beta"):
            coordinate_phase_nd(sel, 2)  # beta = 4 - 4 = 0

    def test_windowed_in_aggregate(self):
        n, f = 11, 2
        tree = _tree(n)
        a_full, _ = distributed_aggregate(tree, f, "bulyan-geomed")
        a_win, _ = distributed_aggregate(tree, f, "bulyan-geomed", window=7)
        for a, w in zip(jax.tree_util.tree_leaves(a_win),
                        jax.tree_util.tree_leaves(a_full)):
            np.testing.assert_allclose(a, w, rtol=1e-6, atol=1e-7)


class TestCoordinatePhaseLowering:
    @pytest.mark.parametrize("gar", ["bulyan-krum", "bulyan-geomed"])
    def test_selected_rows_taken_by_dynamic_slice(self, gar):
        """The selected workers' rows reach the coordinate phase as
        dynamic row slices of each leaf: no op that gathers, sorts or
        cumulatively sums touches a leaf-sized operand.  (The selection
        itself may gather and sort its (n, n) matrix and index vectors;
        the leaves' trailing dims, 13x29 and 31, appear nowhere there.)"""
        n, f = 7, 1
        theta = n - 2 * f
        tree = {"w": jax.ShapeDtypeStruct((n, 13, 29), jnp.float32),
                "b": jax.ShapeDtypeStruct((n, 31), jnp.float32)}
        text = jax.jit(lambda t: distributed_aggregate(t, f, gar)[0]).lower(
            tree).as_text()
        lines = text.splitlines()
        for op in ("stablehlo.gather", "stablehlo.sort", "reduce_window",
                   "stablehlo.while"):
            hits = [l for l in lines if op in l
                    and ("13x29" in l or "x31x" in l or "<31x" in l)]
            assert not hits, hits
        for row in ("tensor<1x13x29xf32>", "tensor<1x31xf32>"):
            slices = [l for l in lines if "stablehlo.dynamic_slice" in l
                      and l.rstrip().endswith(row)]
            assert len(slices) == theta, (row, len(slices))
