"""Tree-aware robust aggregation — the sharded path of every GAR.

The core rules (``repro.core.gars``) consume a flat ``(n, d)`` matrix.
Building that matrix at production scale means concatenating every
parameter shard of every worker into one array — an all-gather of the
full model per step.  This module keeps gradients as pytrees whose leaves
carry a leading worker axis and exploits the structure of the rules:

  * Distance-based selection (Krum, GeoMed, Bulyan phase 1) only needs
    the (n, n) squared-distance matrix.  We accumulate it as a sum of
    per-leaf partial Gram matrices (one tensordot per leaf over all
    trailing dims) — under GSPMD each tensordot contracts the
    model-sharded dims locally and the (n, n) result is all-reduced,
    so the only globally materialized object is n x n.
  * Coordinate-wise phases (cwmed, trimmed mean, Bulyan phase 2) are
    embarrassingly parallel over coordinates and run per-leaf, preserving
    each leaf's sharding.  Bulyan's is one elementwise sweep (a sorting
    network and a prefix-sum window, ``repro.core.bulyan``);
    ``coordinate_phase_nd`` additionally supports windowing over the
    flattened trailing dims to cap the coordinates processed at once.

This module is the *engine* only: the rule bodies themselves live behind
the unified registry (``repro.agg`` — tree implementations in
``repro.agg.tree`` / ``repro.agg.buffered``), and
``distributed_aggregate`` hands them the machinery below through a
``TreeContext``.

Accumulation dtype: the flat reference casts everything to fp32
(``repro.core.pytree.stack_flatten``), so the default here is fp32 too —
bf16 gradients are aggregated in fp32 and cast back.  ``agg_dtype=
"bfloat16"`` is the perf experiment knob (halves distance-pass traffic
on the XLA backend; the Pallas kernel streams the input dtype from HBM
but always *accumulates* fp32 on-chip, so there the knob only thins the
HBM stream and the two backends can differ at bf16 beyond the fp32
parity bound).

Distance backend: the (n, n) matrix is the hot path of every
distance-based GAR, and it has two interchangeable implementations behind
``distance_backend=``:

  "xla"     per-leaf ``jnp.tensordot`` partial Grams (GSPMD shards the
            contraction implicitly) — works everywhere, the semantics
            reference;
  "pallas"  the VMEM-tiled MXU kernel ``repro.kernels.pairwise_gram``.
            With a ``mesh``, each model shard runs the kernel on its local
            d-slice under ``shard_map`` and only the (n, n) partials are
            psum'd — same "no flat (n, d) matrix" invariant, explicit
            tiling.  Falls back to the Pallas interpreter off-TPU so CPU
            CI exercises the identical code path;
  "auto"    "pallas" on TPU when a mesh with a non-trivial model axis is
            threaded through; "xla" everywhere else (see
            ``resolve_distance_backend`` for why the mesh is required);
  "fused"   the single-sweep megakernel ``repro.kernels.fused_agg``:
            ``distributed_aggregate`` reroutes the rule itself onto its
            ``fused-<base>`` registry composite (``repro.agg.fused``),
            so distance accumulation, selection and the coordinate phase
            run in one ``pallas_call`` — no distance matrix round-trips
            HBM on the flat/single-leaf path at all.  With a mesh whose
            ``model`` axis is non-trivial the knob degrades to "pallas"
            (the megakernel has no shard_map partitioning; the
            shard-mapped pair path keeps the semantics).
"""
from __future__ import annotations

import functools
import math
import statistics
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.bulyan import coordinate_phase
from repro.kernels.pairwise_gram import (finalize_dists,
                                         pairwise_gram_partial,
                                         pairwise_gram_tree)
from repro.obs.trace import named_span

__all__ = ["DistAggResult", "coordinate_phase_nd", "distributed_aggregate",
           "inject_byzantine", "pairwise_sq_dists_tree",
           "resolve_distance_backend"]


class DistAggResult(NamedTuple):
    """Per-worker diagnostics of one distributed aggregation (the
    aggregate itself is returned as a pytree alongside)."""

    selected: jnp.ndarray  # (n,) weights of each worker in the output
    scores: jnp.ndarray    # (n,) rule scores (lower = better), or zeros


def _leaves(tree):
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        raise ValueError("empty gradient tree")
    return leaves


def _worker_count(tree) -> int:
    leaves = _leaves(tree)
    n = leaves[0].shape[0]
    for leaf in leaves:
        if leaf.ndim < 1 or leaf.shape[0] != n:
            raise ValueError(
                f"every leaf needs a leading worker axis of {n}, got "
                f"shape {leaf.shape}")
    return n


def _compute_dtype(agg_dtype: str):
    if agg_dtype == "bfloat16":
        return jnp.bfloat16
    if agg_dtype in ("native", "float32"):
        return jnp.float32
    raise ValueError(f"unknown agg_dtype {agg_dtype!r}")


def _trailing_axes(leaf) -> Tuple[int, ...]:
    return tuple(range(1, leaf.ndim))


# ---------------------------------------------------------------------------
# distances
# ---------------------------------------------------------------------------

def resolve_distance_backend(distance_backend: str, mesh=None) -> str:
    """Resolve the user-facing backend knob to a concrete implementation.

    Args:
      distance_backend: ``"xla"`` | ``"pallas"`` | ``"fused"`` |
        ``"auto"``.
      mesh: the mesh that would drive the shard-mapped Pallas pass
        (``None`` when the caller did not thread one through).

    Returns:
      ``"xla"``, ``"pallas"`` or ``"fused"``.  ``"auto"`` picks the
      Pallas kernel only on TPU *and* with a mesh whose ``model`` axis
      is non-trivial: without the mesh the kernel would run as a plain
      ``pallas_call`` inside the GSPMD program, and XLA has no
      partitioning rule for it — it would all-gather every
      model-sharded gradient leaf, exactly the flat materialization this
      module forbids.  Off-TPU the clean fallback is XLA (interpret mode
      is pure-Python per grid step).  An explicit ``"pallas"`` is
      honored as given — opting in without a mesh is the
      single-device/debug path.  ``"fused"`` degrades to ``"pallas"``
      under a non-trivial ``model`` axis for the same partitioning
      reason: the megakernel holds the whole d-tile sweep in one kernel,
      so the shard-mapped pair path takes over on sharded meshes.
    """
    if distance_backend == "auto":
        if jax.default_backend() != "tpu":
            return "xla"
        from repro.dist.mesh import mesh_axis_sizes
        has_model = (mesh is not None
                     and mesh_axis_sizes(mesh).get("model", 1) > 1)
        return "pallas" if has_model else "xla"
    if distance_backend == "fused":
        from repro.dist.mesh import mesh_axis_sizes
        has_model = (mesh is not None
                     and mesh_axis_sizes(mesh).get("model", 1) > 1)
        return "pallas" if has_model else "fused"
    if distance_backend not in ("xla", "pallas"):
        raise ValueError(
            f"distance_backend must be 'xla', 'pallas', 'fused' or "
            f"'auto', got {distance_backend!r}")
    return distance_backend


def _pallas_sharded_dists(tree: Any, mesh, *, block_d: int,
                          interpret: Optional[bool]) -> jnp.ndarray:
    """Shard-mapped Pallas distance pass: each model shard runs the tiled
    kernel on its local d-slice of every leaf, then the (n, n) raw
    partials are psum'd over ``model``.  Worker rows are replicated into
    each shard (an (n, d/model) gather — the same traffic GSPMD's
    implicit sharding of the tensordot path pays), so shards differing
    only in their data/pod coordinate compute identical results and the
    output is replicated.

    Leaves too small/ragged to divide by the model axis enter fully
    replicated (``gram_pspec`` gives them ``P()``): every shard computes
    their whole partial, so those partials must stay *out* of the psum —
    summing them post-reduction instead of multiplying them by the axis
    size."""
    from repro.dist.sharding import gram_pspec

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    if not flat:
        raise ValueError("empty gradient tree")
    leaves = [leaf for _, leaf in flat]
    in_specs = tuple(gram_pspec(leaf.shape, mesh, path)
                     for path, leaf in flat)
    is_sharded = tuple("model" in spec for spec in in_specs)

    def local_partials(*local_leaves):
        n = local_leaves[0].shape[0]
        sharded = jnp.zeros((n, n), jnp.float32)
        replicated = jnp.zeros((n, n), jnp.float32)
        for leaf, shd in zip(local_leaves, is_sharded):
            part = pairwise_gram_partial(
                leaf, block_d=block_d, interpret=interpret)
            if shd:
                sharded = sharded + part
            else:
                replicated = replicated + part
        if "model" in mesh.axis_names:
            sharded = jax.lax.psum(sharded, "model")
        return sharded + replicated

    mapped = jax.shard_map(local_partials, mesh=mesh, in_specs=in_specs,
                           out_specs=P(), check_vma=False)
    return finalize_dists(mapped(*leaves))


def pairwise_sq_dists_tree(tree: Any, compute_dtype=jnp.float32, *,
                           distance_backend: str = "xla", mesh=None,
                           block_d: int = 4096,
                           interpret: Optional[bool] = None) -> jnp.ndarray:
    """Squared euclidean distances over the *concatenation* of all leaves.

    Args:
      tree: pytree of ``(n, *dims)`` worker-stacked gradients (ragged
        trailing dims allowed; every leaf shares the worker axis).
      compute_dtype: accumulation dtype of the ``"xla"`` backend and the
        dtype of the returned matrix (the Pallas kernel always
        accumulates fp32 internally).
      distance_backend: ``"xla"`` | ``"pallas"`` | ``"fused"`` |
        ``"auto"`` — see ``resolve_distance_backend`` (``"fused"`` uses
        the same tiled Pallas accumulation here).
      mesh: optional device mesh.  With the Pallas backend and a mesh
        whose ``model`` axis is non-trivial, the kernel runs per model
        shard under ``shard_map`` and the (n, n) partials are psum'd;
        otherwise the kernel runs on whole (unsharded) leaves.
      block_d: Pallas VMEM tile width (ignored by the XLA backend).
      interpret: Pallas interpret override (``None`` = auto per backend).

    Returns:
      ``(n, n)`` squared distances in ``compute_dtype``, computed as a
      sum of per-leaf partial Gram matrices — no flat (n, d) copy is
      ever built on either backend.
    """
    n = _worker_count(tree)
    backend = resolve_distance_backend(distance_backend, mesh)
    # the "fused" knob reroutes the *rule* (see distributed_aggregate);
    # its distance matrix, when a rule still asks for one, is the same
    # tiled Pallas accumulation
    if backend in ("pallas", "fused"):
        from repro.dist.mesh import mesh_axis_sizes
        if mesh is not None and mesh_axis_sizes(mesh).get("model", 1) > 1:
            d2 = _pallas_sharded_dists(tree, mesh, block_d=block_d,
                                       interpret=interpret)
        else:
            d2 = pairwise_gram_tree(tree, block_d=block_d,
                                    interpret=interpret)
        return d2.astype(compute_dtype)
    gram = jnp.zeros((n, n), compute_dtype)
    sq = jnp.zeros((n,), compute_dtype)
    for leaf in _leaves(tree):
        x = leaf.astype(compute_dtype)
        axes = _trailing_axes(leaf)
        gram = gram + jnp.tensordot(x, x, axes=(axes, axes))
        sq = sq + jnp.sum(x * x, axis=axes)
    return finalize_dists(sq[:, None] + sq[None, :] - 2.0 * gram)


# ---------------------------------------------------------------------------
# coordinate phase over arbitrary trailing dims
# ---------------------------------------------------------------------------

def coordinate_phase_nd(selected: jnp.ndarray, f: int,
                        window: Optional[int] = None) -> jnp.ndarray:
    """Bulyan's coordinate-wise phase over arbitrary trailing dims.

    Args:
      selected: ``(theta, *dims)`` stack of phase-1-selected gradients.
      f: Byzantine bound; requires ``beta = theta - 2f >= 1``.
      window: caps the number of coordinates processed at once, one
        ``coordinate_phase`` sweep per chunk of at most ``window``
        coordinates; ``None`` processes every coordinate in one shot,
        preserving the input's sharding.

    Returns:
      ``(*dims,)`` — per coordinate, the mean of the beta values closest
      to the median (the sorting-network + prefix-sum window form).
    """
    theta = selected.shape[0]
    beta = theta - 2 * f
    if beta < 1:
        raise ValueError(
            f"beta = theta - 2f must be >= 1 (theta={theta}, f={f})")
    trailing = selected.shape[1:]
    d = math.prod(trailing)
    if window is None or window <= 0 or d <= window:
        return coordinate_phase(selected, f)
    flat = selected.reshape(theta, d)
    chunks = [coordinate_phase(flat[:, s:s + window], f)
              for s in range(0, d, window)]
    return jnp.concatenate(chunks, axis=0).reshape(trailing)


# ---------------------------------------------------------------------------
# the engine: registry rules over the sharded distance/coordinate machinery
# ---------------------------------------------------------------------------

def distributed_aggregate(tree: Any, f: int, gar: str = "bulyan-krum", *,
                          agg_dtype: str = "native",
                          window: Optional[int] = None,
                          distance_backend: str = "auto", mesh=None,
                          state=None, history_window: Optional[int] = None,
                          rep_lr: Optional[float] = None,
                          rep_decay: Optional[float] = None):
    """Apply GAR ``gar`` across the leading worker axis of a stacked
    gradient pytree, leaf-wise (semantics contract: equals the flat core
    rule on ``stack_flatten`` of the same tree, see tests/test_dist.py).

    The rule is resolved through the unified registry (``repro.agg``);
    this function only owns the sharded machinery — the distance-backend
    dispatch and the windowed coordinate phase — and hands it to the
    rule's tree implementation through a ``TreeContext``.

    Args:
      tree: pytree of ``(n, *dims)`` worker-stacked gradients.
      f: Byzantine bound the rule defends against (quorum-checked).
      gar: any name ``repro.agg.resolve_rule`` accepts with a tree
        implementation — the registered rules, ``"bulyan-<base>"`` for
        distance-only bases (krum/geomed), and stateful
        ``"buffered-<base>"`` / ``"centered_clip_momentum"`` /
        ``"stale-<base>"`` (staleness weights read from the carried
        state's ``GradientBus``; see ``repro.agg.staleness``).
      agg_dtype: ``"native"`` (fp32) | ``"float32"`` | ``"bfloat16"`` —
        the accumulation dtype contract (see module docstring).
      window: coordinate-phase window for bulyan rules (see
        ``coordinate_phase_nd``).
      distance_backend: ``"xla"`` | ``"pallas"`` | ``"fused"`` |
        ``"auto"`` — how the (n, n) distance matrix of distance-based
        rules is computed (see ``pairwise_sq_dists_tree``; non-distance
        rules ignore it).  ``"fused"`` additionally reroutes the rule
        onto its ``fused-<base>`` megakernel composite when one exists
        (``repro.agg.fused.fused_name``); rules without a fused lowering
        (``brute``, ``average``, ...) run unchanged over the Pallas
        distance pass.
      mesh: optional device mesh for the shard-mapped Pallas path.
      state: carried ``AggState`` for stateful rules (``None``
        zero-initializes one in-graph); stateless rules ignore it.
      history_window: ``buffered-*`` sliding-window length (``None`` =
        registry default).
      rep_lr: ``reputation-*`` EMA rate (``None`` = registry default;
        other rules ignore it — see ``repro.agg.reputation``).
      rep_decay: ``reputation-*`` forgetting factor (same default rule).

    Returns:
      ``(aggregated pytree, DistAggResult)`` for stateless rules, and
      ``(aggregated pytree, DistAggResult, new_state)`` for stateful
      ones — so stateless callers keep the historic two-tuple.  The
      aggregate's leaves keep their input dtypes.
    """
    from repro.agg.registry import TreeContext, resolve_rule
    from repro.agg.specs import check_quorum
    from repro.agg.state import init_state

    n = _worker_count(tree)
    rule = resolve_rule(gar, history_window=history_window,
                        rep_lr=rep_lr, rep_decay=rep_decay)
    check_quorum(gar, n, f, distributed=True,
                 history_window=history_window)
    if resolve_distance_backend(distance_backend, mesh) == "fused":
        from repro.agg.fused import fused_name
        lowered = fused_name(gar)
        if lowered is not None:
            rule = resolve_rule(lowered, history_window=history_window,
                                rep_lr=rep_lr, rep_decay=rep_decay)
    cdt = _compute_dtype(agg_dtype)
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    out_dtypes = [leaf.dtype for leaf in leaves]

    def make_dists(ls):
        t = jax.tree_util.tree_unflatten(treedef, list(ls))
        with named_span("gram"):
            return pairwise_sq_dists_tree(t, cdt,
                                          distance_backend=distance_backend,
                                          mesh=mesh)

    def coordinate(stack, f_):
        with named_span("coordinate"):
            return coordinate_phase_nd(stack, f_, window=window)

    ctx = TreeContext(
        leaves=tuple(leaves), n=n, f=f, cdt=cdt, make_dists=make_dists,
        coordinate_phase=coordinate)

    # every op of the rule lies under ``agg``; the engine's distances and
    # coordinate phase, and the rules' own selection, open one phase each
    # inside it: ``agg/gram``, ``agg/coordinate``, ``agg/select``
    with named_span("agg"):
        if rule.stateful:
            if state is None:
                state = init_state(rule, tree, flat=False)
            out, new_state = rule.tree_fn(ctx, state)
        else:
            out = rule.tree_fn(ctx)
        # the barrier materializes the aggregate here: fused into its
        # consumers, the rule's ops would carry their scope, not ``agg``
        with named_span("coordinate"):
            agg_leaves = jax.lax.optimization_barrier(
                [a.astype(dt) for a, dt in zip(out.leaves, out_dtypes)])

    agg_tree = jax.tree_util.tree_unflatten(treedef, agg_leaves)
    res = DistAggResult(out.selected, out.scores)
    if rule.stateful:
        return agg_tree, res, new_state
    return agg_tree, res


# ---------------------------------------------------------------------------
# per-leaf Byzantine injection
# ---------------------------------------------------------------------------

def _tree_coord_count(leaves) -> int:
    return sum(math.prod(l.shape[1:]) for l in leaves)


def _honest_moments(leaves, n_h: int):
    """Per leaf, the f32 per-coordinate mean of the first ``n_h``
    (honest) rows, and the sum over the leaf's coordinates of their
    population std across those rows (ddof 0, as ``jnp.std``).

    ``n_h`` is static and small, so both come from the same loaded rows,
    one read of each leaf: the mean first, then the squared deviations
    from it (never ``E[x^2] - E[x]^2``, which cancels where the workers
    agree).  The means leave through an optimization barrier, so they
    are written once and read back by whatever consumes them; otherwise
    XLA folds the mean into the consumer's fusion and reads the honest
    rows again there (and, where the consumer updates the stack in
    place, copies them out first).
    """
    means, sums = [], []
    for leaf in leaves:
        rows = [leaf[i].astype(jnp.float32) for i in range(n_h)]
        m = functools.reduce(jnp.add, rows) / n_h
        var = functools.reduce(jnp.add, [(r - m) ** 2 for r in rows]) / n_h
        means.append(m)
        sums.append(jnp.sum(jnp.sqrt(var)))
    return jax.lax.optimization_barrier((means, sums))


def _delta_bar(sd_sums, leaves) -> jnp.ndarray:
    """Paper §B.1 ``delta_bar`` over the concatenated coordinate space,
    from the per-leaf std sums of ``_honest_moments``: 2/sqrt(pi) * mean
    over coordinates of the per-coordinate std across honest workers."""
    total = sum(sd_sums, jnp.zeros((), jnp.float32))
    return 2.0 / jnp.sqrt(jnp.pi) * total / max(_tree_coord_count(leaves), 1)


def inject_byzantine(tree: Any, f: int, attack: str, key=None, *,
                     gar_name: str = "krum", step=None, gamma=None,
                     scale: Optional[float] = None, eps: float = 0.5,
                     z: Optional[float] = None, target: int = 0,
                     coord=0, margin: float = 1.0,
                     direction: str = "ones", prev: Any = None,
                     hold: int = 0, build: int = 5) -> Any:
    """Replace the last ``f`` worker rows of every leaf with Byzantine
    submissions computed from the first ``n - f`` (honest) rows.

    All attacks run per-leaf — coordinate-wise attacks (signflip, alie,
    ipm, zero, mimic, random) are exactly their flat counterparts; the
    omniscient attacks use the paper's §B *closed-form* gamma (the exact
    in-graph bisection of ``repro.core.attacks`` needs the full rule — and
    hence the flat matrix — inside the search loop, so the distributed
    runtime uses the estimate the paper itself used).

    Args:
      tree: pytree of ``(n, *dims)`` worker-stacked gradients.
      f: number of rows to overwrite (``f <= 0`` is a no-op).
      attack: attack name (see module body for the registry).
      key: PRNG key for the ``random`` attack.
      gar_name/step/gamma/scale/eps/z/target/coord/margin/direction:
        per-attack parameters; ``coord`` indexes the concatenated
        coordinate space of the whole tree, or ``"rotate"`` / ``"top"``;
        ``direction`` is the linf attack's +-1 vector — ``"ones"`` or
        ``"anti"`` (against the sign of the honest mean), matching the
        flat ``repro.core.attacks.omniscient_linf``; for
        ``colluding_majority`` it picks the cluster offset instead
        (``"anti"`` = negated honest mean, anything else = random),
        matching the flat attack's ``direction``.
      prev/hold: the delay-exploiting attacks' parameters —
        ``stale_replay`` and ``slow_drift`` read ``prev``, a pytree of
        ``(f, *dims)`` leaves holding the adversary's previous bus
        submissions (threaded by the async step builders; ``None``
        degenerates both to mimic-the-mean), and ``stale_replay``
        re-records every ``hold`` steps (0 = freeze forever).
      build: the ``reputation_burn`` attack's build phase length —
        honest-mean submissions for ``step < build``, then
        ``-scale * mean`` (``colluding_majority`` instead reads ``eps``
        as its offset in delta_bar units; both match the flat
        ``repro.core.attacks`` forms).

    Returns:
      The tree with the last f rows of every leaf replaced, dtypes and
      shapes preserved exactly.
    """
    if f <= 0 or attack == "none":
        return tree
    n = _worker_count(tree)
    n_h = n - f
    if n_h < 1:
        raise ValueError(f"need at least one honest worker (n={n}, f={f})")
    if key is None:
        key = jax.random.PRNGKey(0)
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    # every attack but these reads the honest moments, all from one
    # helper: one read of each leaf's honest rows
    if attack in ("zero", "mimic", "random"):
        means, sd_sums = [], []
    else:
        means, sd_sums = _honest_moments(leaves, n_h)

    def _broadcast(byz_one, leaf):
        """(…) per-leaf Byzantine value -> f stacked rows, leaf dtype."""
        return jnp.broadcast_to(byz_one[None], (f,) + leaf.shape[1:]
                                ).astype(leaf.dtype)

    if attack == "signflip":
        s = 1.0 if scale is None else scale
        byz = [_broadcast(-s * m, l) for m, l in zip(means, leaves)]
    elif attack == "zero":
        byz = [jnp.zeros((f,) + l.shape[1:], l.dtype) for l in leaves]
    elif attack == "mimic":
        byz = [_broadcast(l[:n_h][target], l) for l in leaves]
    elif attack == "ipm":
        byz = [_broadcast(-eps * m, l) for m, l in zip(means, leaves)]
    elif attack == "random":
        s = 10.0 if scale is None else scale  # core.random_noise default
        byz = [s * jax.random.normal(jax.random.fold_in(key, j),
                                     (f,) + l.shape[1:], l.dtype)
               for j, l in enumerate(leaves)]
    elif attack == "alie":
        if z is None:
            s = (n // 2) + 1 - f
            phi = max(min((n - f - s) / float(n - f), 1.0 - 1e-6), 1e-6)
            # in Python, not jnp: a jnp op under a trace is no constant
            z = statistics.NormalDist().inv_cdf(phi)
        # the per-coordinate std, about the helper's means
        byz = [_broadcast(m - z * jnp.sqrt(jnp.mean(
            (l[:n_h].astype(jnp.float32) - m) ** 2, axis=0)), l)
               for m, l in zip(means, leaves)]
    elif attack in ("stale_replay", "slow_drift"):
        t = jnp.asarray(step if step is not None else 0, jnp.int32)
        prev_leaves = (jax.tree_util.tree_leaves(prev)
                       if prev is not None else [None] * len(leaves))
        if len(prev_leaves) != len(leaves):
            raise ValueError(
                "prev must mirror the gradient tree's flat leaf order")
        if attack == "stale_replay":
            s = 1.0 if scale is None else scale
            refresh = t == 0
            if hold > 0:
                refresh = refresh | (t % hold == 0)
            byz = [_broadcast(s * m, l) if p is None
                   else jnp.where(refresh, _broadcast(s * m, l),
                                  p.astype(l.dtype))
                   for m, l, p in zip(means, leaves, prev_leaves)]
        else:
            db = _delta_bar(sd_sums, leaves)
            if direction == "anti":
                es = [jnp.where(m == 0, 1.0, -jnp.sign(m)) for m in means]
            else:
                es = [jnp.ones_like(m) for m in means]
            byz = []
            for m, e, l, p in zip(means, es, leaves, prev_leaves):
                if p is None:
                    byz.append(_broadcast(m + eps * db * e, l))
                else:
                    drifted = p.astype(jnp.float32) + eps * db * e[None]
                    byz.append(jnp.where(t == 0, _broadcast(m, l),
                                         drifted).astype(l.dtype))
    elif attack == "reputation_burn":
        s = 3.0 if scale is None else scale
        t = jnp.asarray(step if step is not None else 0, jnp.int32)
        byz = [_broadcast(jnp.where(t < build, 1.0, -s) * m, l)
               for m, l in zip(means, leaves)]
    elif attack == "colluding_majority":
        # one unit direction over the concatenated coordinate space,
        # normalized by the global norm: random per-leaf gaussians, or
        # (direction="anti") the negated honest mean — the
        # descent-reversing worst case, as in the flat attack
        db = _delta_bar(sd_sums, leaves)
        if direction == "anti":
            dirs = [-m for m in means]
        else:
            dirs = [jax.random.normal(jax.random.fold_in(key, j),
                                      l.shape[1:], jnp.float32)
                    for j, l in enumerate(leaves)]
        norm = jnp.sqrt(sum(jnp.sum(e * e) for e in dirs)) + 1e-12
        byz = [_broadcast(m + eps * db * e / norm, l)
               for m, e, l in zip(means, dirs, leaves)]
    elif attack in ("omniscient_linf", "omniscient_lp"):
        d = _tree_coord_count(leaves)
        db = _delta_bar(sd_sums, leaves)
        # gamma None and "closed" both mean the §B closed form here (the
        # exact bisection only exists on the flat path); margin applies to
        # the estimate only — an explicit gamma is used verbatim
        estimated = gamma is None or gamma == "closed"
        if attack == "omniscient_linf":
            # per-coordinate leeway ~ delta_bar (§3.3: poisoning every
            # coordinate forfeits the sqrt(d) amplification)
            g = (db * margin if estimated
                 else jnp.asarray(gamma, jnp.float32))
            if direction == "anti":
                # against the sign of the honest mean, zeros -> +1
                # (the flat reference's worst-case +-1 vector)
                es = [jnp.where(m == 0, 1.0, -jnp.sign(m)) for m in means]
            else:
                es = [jnp.ones_like(m) for m in means]
            byz = [_broadcast(m + g * e, l)
                   for m, e, l in zip(means, es, leaves)]
        else:
            # §3.2: one coordinate, gamma_m ~ d^{1/p} closed form (§B).
            # ``coord`` indexes the concatenated coordinate space of the
            # whole tree (same convention as the flat reference).
            from repro.core.attacks import _closed_gamma
            g = (_closed_gamma(gar_name, d, f, db) * margin if estimated
                 else jnp.asarray(gamma, jnp.float32))
            sign = jnp.asarray(1.0, jnp.float32)
            if coord == "rotate":
                c = (jnp.asarray(step, jnp.int32) if step is not None
                     else jnp.zeros((), jnp.int32)) % d
            elif coord == "top":
                # coordinate where the honest mean is largest in
                # magnitude, attacked against its sign
                sizes = [math.prod(l.shape[1:]) for l in leaves]
                offs_py = [0]
                for s_ in sizes[:-1]:
                    offs_py.append(offs_py[-1] + s_)
                maxes = jnp.stack([jnp.max(jnp.abs(m)) for m in means])
                arg = jnp.stack([jnp.argmax(jnp.abs(m.reshape(-1)))
                                 for m in means])
                vals = jnp.stack([m.reshape(-1)[a]
                                  for m, a in zip(means, arg)])
                j = jnp.argmax(maxes)
                c = (jnp.asarray(offs_py, jnp.int32)[j]
                     + arg[j].astype(jnp.int32))
                sign = -jnp.sign(vals[j])
            else:
                if isinstance(coord, int) and not 0 <= coord < d:
                    raise ValueError(
                        f"coord must be in [0, {d}), 'rotate' or 'top'; "
                        f"got {coord!r}")
                c = jnp.asarray(coord, jnp.int32)
            off = 0
            byz = []
            for m, l in zip(means, leaves):
                sz = math.prod(l.shape[1:])
                local = c - off
                hit = (local >= 0) & (local < sz)
                e = jnp.zeros((sz,), jnp.float32).at[
                    jnp.clip(local, 0, sz - 1)].set(
                        jnp.where(hit, sign, 0.0)).reshape(l.shape[1:])
                byz.append(_broadcast(m + g * e, l))
                off += sz
    else:
        raise KeyError(f"unknown distributed attack {attack!r}")

    # the f rows written in place: the honest rows stay where
    # ``train/grads`` put them, bit for bit
    out = [l.at[n_h:].set(b) for l, b in zip(leaves, byz)]
    return jax.tree_util.tree_unflatten(treedef, out)
