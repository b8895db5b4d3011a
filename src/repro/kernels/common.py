"""Shared building blocks of the Pallas aggregation kernels.

Three kernels (``pairwise_gram``, ``bulyan_select``, ``coord_stats``)
and the fused megakernel (``fused_agg``) share the same primitives: the
interpret-mode resolution against the active jax backend, the unrolled
odd-even transposition sorting network, and the per-tile combine bodies
(Bulyan's beta-closest-to-median window, the coordinate-wise median and
f-trimmed mean).  They used to be duplicated — or imported sideways,
``coord_stats -> bulyan_select -> pairwise_gram`` — which made every new
kernel deepen the chain.  This module is the single home: kernels import
*down* into ``common`` only, never into each other.

The sorting network and Bulyan's window (``oe_sort_rows``,
``bulyan_window``) are defined in ``repro.core.bulyan``, whose XLA
coordinate phase is built from them, and re-exported here: the kernels
and the plain-jit path run one body.

Every helper is shape-polymorphic over "rows": a list of equally-shaped
arrays treated as axis 0 of a (rows, ...) stack.  Inside a kernel the
rows are ``(block_d,)`` lane vectors; the same code runs on full
``(d,)`` arrays under plain jit, which is what gives the fused kernel a
bitwise-comparable out-of-kernel reference path.
"""
from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp

from repro.core.bulyan import bulyan_window, oe_sort_rows

__all__ = ["bulyan_window", "coord_median", "coord_trimmed_mean",
           "oe_sort_rows", "resolve_interpret"]


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Resolve the ``interpret`` knob against the active jax backend.

    Args:
      interpret: ``True`` / ``False`` to force, ``None`` to pick the
        compiled kernel on TPU and the Pallas interpreter elsewhere
        (CPU CI containers, GPU hosts).

    Returns:
      bool: the concrete interpret flag to hand to ``pl.pallas_call``.
    """
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def coord_median(rows: List[jnp.ndarray]) -> jnp.ndarray:
    """Coordinate-wise median of an already-sorted row list.

    Args:
      rows: ``n`` sorted rows (ascending per element).

    Returns:
      One row: the middle row for odd ``n``, the mean of the two middle
      rows for even ``n`` (matching ``jnp.median(axis=0)``).
    """
    n = len(rows)
    if n % 2:
        return rows[n // 2]
    return 0.5 * (rows[n // 2 - 1] + rows[n // 2])


def coord_trimmed_mean(rows: List[jnp.ndarray], f: int) -> jnp.ndarray:
    """Coordinate-wise f-trimmed mean of an already-sorted row list.

    Args:
      rows: ``n`` sorted rows (ascending per element); requires
        ``n > 2f``.
      f: trim count per side.

    Returns:
      One row: the mean of rows ``f .. n - f - 1``.
    """
    n = len(rows)
    acc = rows[f]
    for r in rows[f + 1:n - f]:
        acc = acc + r
    return acc / (n - 2 * f)
