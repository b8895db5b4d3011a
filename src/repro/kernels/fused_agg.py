"""Pallas TPU megakernel: Gram -> select -> coordinate phase, one sweep.

The robust-aggregation hot path used to be three kernels chained through
HBM — ``pairwise_gram`` (distances), a host-side selection, then
``bulyan_select`` / ``coord_stats`` on a gathered ``(theta, d)`` copy.
Every stage re-streams O(n * d) bytes.  This module fuses them into a
single ``pallas_call`` over a two-phase grid:

  phase 0 (distance sweep): each step loads one ``(n, block_d)`` slab,
      computes the partial ``|x|^2 + |y|^2 - 2 x.yT`` on the MXU and
      accumulates it into an ``(n, n)`` raw-Gram block that stays
      resident in VMEM across steps (same structure as
      ``pairwise_gram``);

  phase 1 (select + combine): at the first step the resident raw Gram
      is finalized and the selection runs *in-kernel* — Krum scores via
      the odd-even network over the symmetric distance matrix, Bulyan's
      recursive extraction as a statically unrolled masked-argmin loop —
      leaving a ``(theta, n)`` one-hot weight block in VMEM.  Every
      phase-1 step then re-loads its slab, gathers the selected rows as
      an exact one-hot f32 matmul and applies the coordinate phase
      (``bulyan_window`` / mean) before writing the ``(1, block_d)``
      output tile.

HBM traffic per aggregation: read ``2 * n * d`` (two input sweeps),
write ``d`` — versus ``>= 3 n d + 2 theta d`` for the chained kernels.
No ``(theta, d)`` gather and no intermediate distance round-trip ever
touch HBM; only the tiny ``(n, n)`` / ``(theta, n)`` diagnostics do.
Inputs stream in their native dtype (bf16 at production scale) and all
accumulation is fp32 on-chip — the same contract the other kernels
honour, probed by ``repro.kernels.probes.fused_fp32_contract_error``.

Selection is TPU-safe by construction: no ``argsort`` / ``argmin`` /
1-D iota in the kernel body.  Sorted neighbour distances come from the
odd-even network applied across the *rows* of the symmetric distance
matrix (the k-th smallest of column j equals the k-th smallest of row
j); first-index argmins are built from 2-D ``broadcasted_iota`` + min
reductions; availability masks are ``(1, n)`` float vectors updated in
statically unrolled Python loops — mirroring ``repro.core.bulyan``'s
remaining-index recursion pick for pick.

Multi-leaf gradient trees use the tight kernel *pair* instead: the
per-leaf ``pairwise_gram_partial`` accumulation (leaves sum raw
partials), the same :func:`select_weights` helper under plain jit, and
:func:`fused_coordinate` per leaf — select + coordinate phase in one
kernel, still without materializing a ``(theta, d)`` gather.  Because
the in-kernel and out-of-kernel paths share one selection function, the
two lowerings are bitwise-comparable (``tests/test_fused_agg.py``).

Exposed to the stack as ``distance_backend="fused"`` (see
``repro.dist.robust``) and as the ``fused-<base>`` registry composites
(``repro.agg.fused``).  Design notes and the tiling diagram live in
docs/kernels.md.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import (bulyan_window, coord_median,
                                  coord_trimmed_mean, oe_sort_rows,
                                  resolve_interpret)

__all__ = ["COORD_MODES", "DIST_MODES", "FUSED_MODES", "fused_aggregate",
           "fused_coordinate", "select_weights"]

#: modes whose selection consumes the (n, n) distance matrix
DIST_MODES: Tuple[str, ...] = ("bulyan-geomed", "bulyan-krum", "geomed",
                               "krum", "multikrum")

#: coordinate-only modes (no distance phase at all)
COORD_MODES: Tuple[str, ...] = ("cwmed", "trimmed_mean")

#: every mode the fused kernels lower (== repro.agg.fused.FUSED_BASES)
FUSED_MODES: Tuple[str, ...] = tuple(sorted(DIST_MODES + COORD_MODES))

#: unrolled sort/selection networks are O(n^2)-O(n^3) ops at trace time
_MAX_N = 64


def _weight_rows(n: int, f: int, mode: str) -> int:
    """Row count of the selection-weight matrix for one mode."""
    return n - 2 * f if mode.startswith("bulyan") else 1


def _check_mode_shape(n: int, f: int, mode: str) -> None:
    """Trace-time structural checks shared by both kernel entry points."""
    if mode not in FUSED_MODES:
        raise KeyError(f"unknown fused mode {mode!r}; have "
                       f"{sorted(FUSED_MODES)}")
    if n > _MAX_N:
        raise ValueError(
            f"fused kernels unroll sort/select networks: n <= {_MAX_N} "
            f"(got n={n})")
    if mode.startswith("bulyan") and n < 4 * f + 3:
        raise ValueError(f"bulyan requires n >= 4f+3, got n={n}, f={f}")
    if mode in ("krum", "multikrum") and n - f - 2 < 1:
        raise ValueError(
            f"krum needs n >= f + 3 per use (n={n}, f={f})")
    if mode == "trimmed_mean" and n <= 2 * f:
        raise ValueError(f"need n > 2f (n={n}, f={f})")


# ---------------------------------------------------------------------------
# selection on the (n, n) distance matrix — shared in-/out-of-kernel
# ---------------------------------------------------------------------------

def _iota_row(n: int) -> jnp.ndarray:
    """(1, n) int32 lane indices (2-D iota: TPU kernels reject 1-D)."""
    return jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)


def _first_argmin_onehot(scores: jnp.ndarray, n: int) -> jnp.ndarray:
    """(1, n) scores -> (1, n) f32 one-hot at the first (smallest-index)
    minimum — the argmin convention of every selection rule in the repo."""
    iota = _iota_row(n)
    m = jnp.min(scores)
    idx = jnp.min(jnp.where(scores == m, iota, n))
    return (iota == idx).astype(jnp.float32)


def _masked_dists(d2: jnp.ndarray, avail: jnp.ndarray,
                  n: int) -> jnp.ndarray:
    """Diagonal and rows/cols of unavailable workers -> +inf (the
    ``repro.core.gars._masked`` convention, iota/outer-product form)."""
    vmat = jax.lax.dot_general(
        avail, avail, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)           # (n, n) outer product
    r = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return jnp.where((r == c) | (vmat < 0.5), jnp.inf, d2)


def _krum_scores(dm: jnp.ndarray, avail: jnp.ndarray, f: int, n_rem: int,
                 n: int) -> jnp.ndarray:
    """Krum scores on a masked matrix: per worker, the sum of the
    ``k = max(1, n_rem - f - 2)`` smallest remaining distances.  The
    matrix is symmetric, so sorting across its *rows* with the odd-even
    network yields each column's (== each worker's) sorted neighbour
    distances without a per-row sort."""
    k = max(1, n_rem - f - 2)
    cols = oe_sort_rows([dm[i:i + 1, :] for i in range(n)])
    s = cols[0]
    for r in cols[1:k]:
        s = s + r
    return jnp.where(avail > 0.5, s, jnp.inf)


def _geomed_scores(dm: jnp.ndarray, avail: jnp.ndarray,
                   n: int) -> jnp.ndarray:
    """Medoid scores: per worker, the sum of non-squared distances to the
    remaining workers (masked +inf entries contribute zero, as in
    ``repro.core.gars.geomed_scores``); axis-0 sum == axis-1 sum by
    symmetry and keeps the result a (1, n) lane vector."""
    dist = jnp.sqrt(jnp.where(jnp.isinf(dm), 0.0, dm))
    s = jnp.sum(dist, axis=0, keepdims=True)
    return jnp.where(avail > 0.5, s, jnp.inf)


def select_weights(dist2: jnp.ndarray, n: int, f: int, mode: str):
    """Selection weights of one fused mode from finalized distances.

    This single function is the selection semantics of the fused path:
    the megakernel calls it on the VMEM-resident distance block, and the
    multi-leaf tree path calls it under plain jit on the all-reduced
    matrix — so the two lowerings are bitwise-identical by construction.
    Every op is TPU-kernel-safe (2-D iota, min/max networks, one-hot
    matmuls; no argsort/argmin/gather).

    Args:
      dist2: ``(n, n)`` finalized squared distances (non-negative, zero
        diagonal), any float dtype.
      n: worker count (static).
      f: Byzantine bound (static).
      mode: one of :data:`DIST_MODES` — ``"krum"`` / ``"geomed"``
        (one-hot winner), ``"multikrum"`` (uniform over the m best
        scores), ``"bulyan-krum"`` / ``"bulyan-geomed"`` (the theta
        = n - 2f recursive picks, mirroring
        ``repro.core.bulyan.select_indices_from_dists``).

    Returns:
      ``(weights, selected, scores)``: ``weights`` is the
      ``(theta_w, n)`` f32 combination matrix (``theta_w`` rows of
      one-hots for bulyan, one row of convex weights otherwise),
      ``selected`` the ``(1, n)`` diagnostic marks (convex weights, or
      1.0 per bulyan pick), ``scores`` the ``(1, n)`` rule scores
      (zeros for bulyan, matching the dense composites).
    """
    d2 = dist2.astype(jnp.float32)
    avail = jnp.ones((1, n), jnp.float32)
    if mode in ("krum", "geomed"):
        dm = _masked_dists(d2, avail, n)
        scores = (_krum_scores(dm, avail, f, n, n) if mode == "krum"
                  else _geomed_scores(dm, avail, n))
        hot = _first_argmin_onehot(scores, n)
        return hot, hot, scores
    if mode == "multikrum":
        scores = _krum_scores(_masked_dists(d2, avail, n), avail, f, n, n)
        m = max(1, n - f - 2)
        acc = jnp.zeros((1, n), jnp.float32)
        cur = scores
        for _ in range(m):
            hot = _first_argmin_onehot(cur, n)
            acc = acc + hot
            cur = jnp.where(hot > 0.5, jnp.inf, cur)
        w = acc / m
        return w, w, scores
    if mode not in ("bulyan-krum", "bulyan-geomed"):
        raise KeyError(f"select_weights needs a distance mode, got "
                       f"{mode!r}")
    base = mode.split("-", 1)[1]
    theta = n - 2 * f
    picks = []
    sel = jnp.zeros((1, n), jnp.float32)
    for t in range(theta):
        n_rem = n - t
        dm = _masked_dists(d2, avail, n)
        scores = (_krum_scores(dm, avail, f, n_rem, n) if base == "krum"
                  else _geomed_scores(dm, avail, n))
        hot = _first_argmin_onehot(scores, n)
        picks.append(hot)
        sel = sel + hot
        avail = avail - hot
    w = jnp.concatenate(picks, axis=0)                # (theta, n)
    return w, sel, jnp.zeros((1, n), jnp.float32)


# ---------------------------------------------------------------------------
# per-tile combine — shared by the megakernel and the pair kernel
# ---------------------------------------------------------------------------

def _finalized(raw: jnp.ndarray, n: int) -> jnp.ndarray:
    """In-kernel ``finalize_dists``: clamp fp-cancellation negatives and
    zero the diagonal (iota-built identity; same value order as the
    ``jnp.eye`` form used outside kernels)."""
    r = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    eye = (r == c).astype(jnp.float32)
    return jnp.maximum(raw, 0.0) * (1.0 - eye)


def _combine_tile(x: jnp.ndarray, w: Optional[jnp.ndarray], n: int, f: int,
                  mode: str) -> jnp.ndarray:
    """One output tile from one (n, block_d) f32 slab.

    Coordinate modes sort the worker rows directly; distance modes first
    contract with the selection weights — an exact row gather when the
    weights are one-hot f32 and the product is full f32 (the MXU's
    default precision rounds ``x`` to bf16 on a TPU) — then run the
    mode's reduction."""
    if mode in COORD_MODES:
        rows = oe_sort_rows([x[i] for i in range(n)])
        out = (coord_median(rows) if mode == "cwmed"
               else coord_trimmed_mean(rows, f))
        return out[None, :]
    y = jax.lax.dot_general(
        w, x, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)           # (theta_w, block_d)
    if mode.startswith("bulyan"):
        rows = oe_sort_rows([y[t] for t in range(y.shape[0])])
        return bulyan_window(rows, f)[None, :]
    return y                                          # (1, block_d) mean


# ---------------------------------------------------------------------------
# the megakernel (flat / single-leaf path)
# ---------------------------------------------------------------------------

def _make_megakernel(n: int, f: int, mode: str):
    def kernel(g_ref, agg_ref, sel_ref, score_ref, raw_ref, w_ref):
        p = pl.program_id(0)
        i = pl.program_id(1)
        x = g_ref[...].astype(jnp.float32)            # (n, block_d)

        @pl.when(p == 0)
        def _gram():
            sq = jnp.sum(x * x, axis=1)
            gram = jax.lax.dot_general(
                x, x, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)   # (n, n) on the MXU
            part = sq[:, None] + sq[None, :] - 2.0 * gram

            @pl.when(i == 0)
            def _init():
                raw_ref[...] = part

            @pl.when(i > 0)
            def _acc():
                raw_ref[...] += part

        @pl.when((p == 1) & (i == 0))
        def _select():
            d2 = _finalized(raw_ref[...], n)
            w, sel, scores = select_weights(d2, n, f, mode)
            w_ref[...] = w
            sel_ref[...] = sel
            score_ref[...] = scores

        @pl.when(p == 1)
        def _combine():
            agg_ref[...] = _combine_tile(x, w_ref[...], n, f, mode)

    return kernel


@functools.partial(jax.jit, static_argnames=("f", "mode", "block_d",
                                             "interpret"))
def fused_aggregate(grads: jnp.ndarray, f: int, *,
                    mode: str = "bulyan-krum", block_d: int = 2048,
                    interpret: Optional[bool] = None):
    """Robust-aggregate a flat worker stack in one fused kernel sweep.

    Args:
      grads: ``(n, d)`` worker-stacked flat gradients, any float dtype
        (bf16 streams thin from HBM; accumulation is fp32 in-kernel).
      f: Byzantine bound (static; quorum structure checked at trace
        time, mirroring the dense rules' own checks).
      mode: one of :data:`FUSED_MODES` — the base-rule name the kernel
        lowers (``"krum"``, ``"multikrum"``, ``"geomed"``, ``"cwmed"``,
        ``"trimmed_mean"``, ``"bulyan-krum"``, ``"bulyan-geomed"``).
      block_d: VMEM tile width along d.
      interpret: ``None`` resolves per backend (compiled on TPU, the
        Pallas interpreter elsewhere); see
        ``repro.kernels.common.resolve_interpret``.

    Returns:
      ``(gradient, selected, scores)``: the ``(d,)`` f32 aggregate, the
      ``(n,)`` f32 selection weights and the ``(n,)`` f32 rule scores —
      the same triple the dense registry rules report.
    """
    n, d = grads.shape
    _check_mode_shape(n, f, mode)
    if mode in COORD_MODES:
        agg = fused_coordinate(grads, None, f, mode=mode, block_d=block_d,
                               interpret=interpret)
        return (agg, jnp.full((n,), 1.0 / n, jnp.float32),
                jnp.zeros((n,), jnp.float32))
    block_d = min(block_d, max(d, 128))
    pad = (-d) % block_d
    if pad:
        # zero padding adds |0-0|^2 = 0 to every distance, and padded
        # output columns are sliced off below: exact
        grads = jnp.pad(grads, ((0, 0), (0, pad)))
    dp = grads.shape[1]
    theta_w = _weight_rows(n, f, mode)
    agg, sel, scores, _raw, _w = pl.pallas_call(
        _make_megakernel(n, f, mode),
        grid=(2, dp // block_d),
        in_specs=[pl.BlockSpec((n, block_d), lambda p, i: (0, i))],
        out_specs=(
            # parks on tile 0 during the distance sweep (p = 0), then
            # walks the tiles — so no phase-0 step ever flushes garbage
            pl.BlockSpec((1, block_d), lambda p, i: (0, i * p)),
            pl.BlockSpec((1, n), lambda p, i: (0, 0)),
            pl.BlockSpec((1, n), lambda p, i: (0, 0)),
            pl.BlockSpec((n, n), lambda p, i: (0, 0)),
            pl.BlockSpec((theta_w, n), lambda p, i: (0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((1, dp), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            # VMEM-resident accumulators (raw Gram, selection weights):
            # declared as outputs so they persist across grid steps —
            # only the (n, n)-sized diagnostics ever reach HBM
            jax.ShapeDtypeStruct((n, n), jnp.float32),
            jax.ShapeDtypeStruct((theta_w, n), jnp.float32),
        ),
        interpret=resolve_interpret(interpret),
    )(grads)
    return agg[0, :d], sel[0], scores[0]


# ---------------------------------------------------------------------------
# the pair kernel (multi-leaf tree path): select + coordinate in one pass
# ---------------------------------------------------------------------------

def _make_pair_kernel(n: int, f: int, mode: str):
    if mode in COORD_MODES:
        def kernel(g_ref, agg_ref):
            x = g_ref[...].astype(jnp.float32)
            agg_ref[...] = _combine_tile(x, None, n, f, mode)
    else:
        def kernel(g_ref, w_ref, agg_ref):
            x = g_ref[...].astype(jnp.float32)
            agg_ref[...] = _combine_tile(x, w_ref[...], n, f, mode)
    return kernel


@functools.partial(jax.jit, static_argnames=("f", "mode", "block_d",
                                             "interpret"))
def fused_coordinate(stack: jnp.ndarray, weights: Optional[jnp.ndarray],
                     f: int, *, mode: str = "bulyan-krum",
                     block_d: int = 2048,
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """Selection-combine + coordinate phase of one leaf, one kernel.

    The multi-leaf half of the fused lowering: distances come from the
    per-leaf ``pairwise_gram_partial`` accumulation (or the engine's
    backend closure), :func:`select_weights` turns them into a weight
    matrix once, and this kernel applies weight-gather and coordinate
    reduction per leaf — the ``(theta, d)`` gather of the unfused
    ``bulyan_select`` path never materializes.

    Args:
      stack: ``(n, d)`` worker-stacked leaf slab, any float dtype.
      weights: ``(theta_w, n)`` f32 selection weights from
        :func:`select_weights`; ``None`` for the coordinate-only modes
        (which sort the worker rows directly).
      f: Byzantine bound (static).
      mode: one of :data:`FUSED_MODES`.
      block_d: VMEM tile width along d.
      interpret: ``None`` resolves per backend.

    Returns:
      ``(d,)`` f32 aggregated coordinates of this leaf.
    """
    n, d = stack.shape
    _check_mode_shape(n, f, mode)
    coord_only = mode in COORD_MODES
    if coord_only != (weights is None):
        raise ValueError(
            f"mode {mode!r} {'takes no' if coord_only else 'needs'} "
            f"selection weights")
    block_d = min(block_d, max(d, 128))
    pad = (-d) % block_d
    if pad:
        stack = jnp.pad(stack, ((0, 0), (0, pad)))
    dp = stack.shape[1]
    in_specs = [pl.BlockSpec((n, block_d), lambda i: (0, i))]
    operands = [stack]
    if not coord_only:
        theta_w = _weight_rows(n, f, mode)
        in_specs.append(pl.BlockSpec((theta_w, n), lambda i: (0, 0)))
        operands.append(weights.astype(jnp.float32))
    out = pl.pallas_call(
        _make_pair_kernel(n, f, mode),
        grid=(dp // block_d,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, dp), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(*operands)
    return out[0, :d]
