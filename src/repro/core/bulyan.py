"""Bulyan(A) — the paper's contribution (§4).

Two phases:

1. *Recursive selection*: repeatedly run the base (alpha, f)-Byzantine-
   resilient rule ``A`` on the remaining received set, each time moving the
   proposed vector closest to A's output into the selection set, until
   theta = n - 2f vectors are selected.  For Krum / the Medoid, "closest to
   A's output" is exactly A's output index.  Pairwise distances are computed
   once and sub-indexed across iterations (Proposition 1's amortization).

2. *Coordinate-wise aggregation*: for each coordinate i, output the average
   of the beta = theta - 2f values closest to the coordinate-wise median
   (the median being the minimizer, among proposed values, of the sum of
   absolute deviations — a 1-D medoid).

The coordinate phase is exposed standalone (``coordinate_phase``) because it
is what the Pallas kernels (``repro.kernels.bulyan_select``,
``repro.kernels.fused_agg``) and the model-axis-sharded distributed
implementation (``repro.dist.robust``) run: it is embarrassingly parallel
over coordinates.  It is one elementwise sweep over the theta rows — an
unrolled sorting network (``oe_sort_rows``) and a prefix-sum window
(``bulyan_window``) — with no sort, cumulative sum or gather along the
worker axis, so XLA fuses it (and the row slices feeding it) into a single
pass, and the kernels run the very same body on their VMEM tiles.

Note on the recursion depth: with theta = n - 2f iterations the last call to
A sees 2f + 1 vectors.  Krum's neighbour count n' - f - 2 can then reach 0
(for f <= 1), so we clamp it to >= 1 — matching the reference
implementation's behaviour (LPD-EPFL/bulyan).
"""
from __future__ import annotations

import itertools
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp

from repro.core import gars
from repro.core.types import AggResult


def _krum_pos(sub: jnp.ndarray, f: int, n_rem: int) -> jnp.ndarray:
    """Krum winner position on an (n_rem, n_rem) distance submatrix."""
    k = max(1, n_rem - f - 2)
    dm = sub + jnp.where(jnp.eye(n_rem, dtype=bool), jnp.inf, 0.0)
    snn = jnp.sort(dm, axis=1)[:, :k]
    return jnp.argmin(jnp.sum(snn, axis=1))


def _geomed_pos(sub: jnp.ndarray, n_rem: int) -> jnp.ndarray:
    dist = jnp.sqrt(jnp.maximum(sub, 0.0))
    return jnp.argmin(jnp.sum(dist, axis=1))


def _brute_pos(sub: jnp.ndarray, grads_rem: jnp.ndarray, f: int,
               n_rem: int) -> jnp.ndarray:
    """Brute on the remaining set: min-diameter subset of size n_rem - f,
    output = subset average; winner = remaining vector closest to it."""
    size = n_rem - f
    subsets = jnp.asarray(list(itertools.combinations(range(n_rem), size)))
    block = sub[subsets[:, :, None], subsets[:, None, :]]
    diam = jnp.max(block.reshape(subsets.shape[0], -1), axis=1)
    best = subsets[jnp.argmin(diam)]  # (size,)
    out = jnp.mean(grads_rem[best], axis=0)
    d2 = jnp.sum((grads_rem - out[None, :]) ** 2, axis=1)
    return jnp.argmin(d2)


def select_indices_from_dists(dist2: jnp.ndarray, f: int,
                              base: str = "krum") -> jnp.ndarray:
    """Phase 1 for distance-only bases (krum/geomed): (theta,) indices from
    the (n, n) squared-distance matrix alone.  This is what the distributed
    runtime uses — the matrix is tiny and replicated after an all-reduce of
    per-shard partial distances (see repro.dist.robust)."""
    n = dist2.shape[0]
    theta = n - 2 * f
    if n < 4 * f + 3:
        raise ValueError(f"bulyan requires n >= 4f+3, got n={n}, f={f}")
    if base not in ("krum", "geomed"):
        raise KeyError(f"distance-only selection needs krum/geomed, "
                       f"got {base!r}")
    rem = jnp.arange(n)
    picked = []
    for t in range(theta):
        n_rem = n - t
        sub = dist2[rem[:, None], rem[None, :]]
        pos = (_krum_pos(sub, f, n_rem) if base == "krum"
               else _geomed_pos(sub, n_rem))
        picked.append(rem[pos])
        rem = jnp.delete(rem, pos, assume_unique_indices=True)
    return jnp.stack(picked)


def select_indices(grads: jnp.ndarray, f: int, base: str = "krum",
                   dist2: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Phase 1: (theta,) original-worker indices chosen by the recursion.

    Unrolled in Python — theta = n - 2f is static and small (worker counts
    are <= a few dozen).  A *remaining-index array* maps static subset
    enumeration / static loop bounds onto the dynamically shrinking set.
    """
    n = grads.shape[0]
    theta = n - 2 * f
    if n < 4 * f + 3:
        raise ValueError(f"bulyan requires n >= 4f+3, got n={n}, f={f}")
    if dist2 is None:
        dist2 = gars.pairwise_sq_dists(grads)

    rem = jnp.arange(n)
    picked = []
    for t in range(theta):
        n_rem = n - t
        sub = dist2[rem[:, None], rem[None, :]]  # (n_rem, n_rem)
        if base == "krum":
            pos = _krum_pos(sub, f, n_rem)
        elif base == "geomed":
            pos = _geomed_pos(sub, n_rem)
        elif base == "average":
            out = jnp.mean(grads[rem], axis=0)
            pos = jnp.argmin(jnp.sum((grads[rem] - out[None, :]) ** 2, axis=1))
        elif base == "brute":
            pos = _brute_pos(sub, grads[rem], f, n_rem)
        else:
            raise KeyError(f"unsupported bulyan base {base!r}")
        picked.append(rem[pos])
        rem = jnp.delete(rem, pos, assume_unique_indices=True)
    return jnp.stack(picked)


def oe_sort_rows(rows: List[jnp.ndarray]) -> List[jnp.ndarray]:
    """Odd-even transposition sort across a list of rows (axis 0).

    Fully unrolled for the static row count (worker counts are <= a few
    dozen): no data-dependent control flow, exactly ``m * (m - 1) / 2``
    min/max pairs on the row vectors — elementwise work XLA fuses into
    one pass, and the TPU-safe substitute for ``jnp.sort(axis=0)``
    inside a kernel body.

    Args:
      rows: list of equally-shaped arrays, one per row of the stack
        being sorted (``(block_d,)`` lane vectors inside a kernel).

    Returns:
      New list with the rows sorted ascending per element (the inputs
      are not mutated).
    """
    m = len(rows)
    rows = list(rows)
    for p in range(m):
        for i in range(p % 2, m - 1, 2):
            a, b = rows[i], rows[i + 1]
            rows[i] = jnp.minimum(a, b)
            rows[i + 1] = jnp.maximum(a, b)
    return rows


def bulyan_window(rows: List[jnp.ndarray], f: int) -> jnp.ndarray:
    """Bulyan's coordinate phase on an already-sorted row list.

    Per element: the mean of the ``beta = theta - 2f`` sorted values
    closest to the median (the lower-middle row).  The beta-closest set
    is a *contiguous window* of the sorted order, so it reduces to
    running prefix sums of the values and of ``|x - med|`` plus a
    strict-``<`` ``where`` chain over the ``theta - beta + 1`` windows
    (the first window wins ties) — no gather, no second sort.

    Args:
      rows: ``theta`` sorted rows (ascending per element), e.g. the
        output of :func:`oe_sort_rows`.
      f: Byzantine bound; requires ``beta = theta - 2f >= 1``.

    Returns:
      One row: per element, the best window mean.
    """
    theta = len(rows)
    beta = theta - 2 * f
    med = rows[(theta - 1) // 2]

    if beta == theta:
        acc = rows[0]
        for r in rows[1:]:
            acc = acc + r
        return acc / beta

    # prefix sums of sorted values and |sorted - med|
    pref_v = [jnp.zeros_like(med)]
    pref_d = [jnp.zeros_like(med)]
    for r in rows:
        pref_v.append(pref_v[-1] + r)
        pref_d.append(pref_d[-1] + jnp.abs(r - med))

    n_win = theta - beta + 1
    best_dev = pref_d[beta] - pref_d[0]
    best_sum = pref_v[beta] - pref_v[0]
    for w in range(1, n_win):
        dev = pref_d[w + beta] - pref_d[w]
        s = pref_v[w + beta] - pref_v[w]
        take = dev < best_dev                      # first-window tiebreak
        best_dev = jnp.where(take, dev, best_dev)
        best_sum = jnp.where(take, s, best_sum)
    return best_sum / beta


def coordinate_phase(selected: jnp.ndarray, f: int) -> jnp.ndarray:
    """Phase 2 on a (theta, ...) stack: per-coordinate average of the beta
    values closest to the coordinate-wise median.

    One elementwise sweep: the theta rows go through the unrolled
    sorting network (:func:`oe_sort_rows`), and the beta values closest
    to the median — a contiguous window of the sorted order — are found
    by :func:`bulyan_window`'s prefix sums.  No sort, cumulative sum or
    gather runs along the worker axis, so under jit the whole phase (and
    any row slices or casts producing ``selected``) is one fusion.  With
    ``f = 0`` (``beta == theta``) it is the mean of the sorted values,
    summed in order as the Pallas kernels sum them.
    """
    theta = selected.shape[0]
    beta = theta - 2 * f
    if beta < 1:
        raise ValueError(
            f"beta = theta - 2f must be >= 1 (theta={theta}, f={f})")
    return bulyan_window(oe_sort_rows([selected[i] for i in range(theta)]),
                         f)


def coordinate_phase_ref(selected: jnp.ndarray, f: int) -> jnp.ndarray:
    """Literal transcription of the paper's formula (argsort of |x - med|);
    independent oracle for the sorting-network implementation and the
    Pallas kernels.  Ties (measure-zero for float inputs) may resolve differently.
    """
    theta = selected.shape[0]
    beta = theta - 2 * f
    s = jnp.sort(selected, axis=0)
    med = s[(theta - 1) // 2]
    dist = jnp.abs(selected - med[None, :])
    order = jnp.argsort(dist, axis=0)[:beta]  # (beta, d)
    closest = jnp.take_along_axis(selected, order, axis=0)
    return jnp.mean(closest, axis=0)


def make_bulyan(base: str = "krum",
                coordinate_impl: Optional[Callable] = None):
    """Build Bulyan(base) as a standard GAR callable."""
    cp = coordinate_impl or coordinate_phase

    def bulyan(grads: jnp.ndarray, f: int) -> AggResult:
        n = grads.shape[0]
        idx = select_indices(grads, f, base=base)
        selected = grads[idx]  # (theta, d)
        agg = cp(selected, f)
        sel = jnp.zeros((n,), grads.dtype).at[idx].set(1.0)
        return AggResult(agg, sel, jnp.zeros((n,), grads.dtype))

    bulyan.__name__ = f"bulyan_{base}"
    return bulyan
