"""Pallas TPU kernel: Bulyan's coordinate-wise phase, fused.

Per coordinate: median of the theta selected values (1-D medoid = the
lower-middle order statistic), then the average of the beta = theta - 2f
values closest to it.  This is pure VPU work over d coordinates — the
memory-bound hot loop of Bulyan (Proposition 1's ``O(d n)`` term), so the
kernel's job is to stream d through VMEM in blocks and do everything for a
block in registers:

  * the sort is an odd-even transposition network, fully unrolled for the
    static worker count theta (<= ~32): no data-dependent control flow,
    exactly theta*(theta-1)/2 min/max pairs on (block_d,)-wide lanes;
  * the "beta closest to the median" set is a *contiguous window* of the
    sorted order, so it reduces to prefix sums + an unrolled argmin over
    theta - beta + 1 windows — no gather, no second sort;
  * one fused pass: HBM traffic = read theta*block_d, write block_d.

The body is ``repro.core.bulyan``'s own (``oe_sort_rows`` +
``bulyan_window``, re-exported by ``repro.kernels.common``): the XLA
coordinate phase runs the same code on whole rows, so the two agree
bitwise.

Grid = (d / block_d,); blocks are fully independent (embarrassingly parallel
over coordinates — the same fact that lets the distributed runtime shard
this phase over the `model` mesh axis).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import (bulyan_window, oe_sort_rows,
                                  resolve_interpret)

__all__ = ["bulyan_select"]


def _make_kernel(theta: int, f: int):
    def kernel(sel_ref, out_ref):
        x = sel_ref[...].astype(jnp.float32)          # (theta, block_d)
        rows = oe_sort_rows([x[i] for i in range(theta)])
        out_ref[...] = bulyan_window(rows, f)[None, :]

    return kernel


@functools.partial(jax.jit, static_argnames=("f", "block_d", "interpret"))
def bulyan_select(selected: jnp.ndarray, f: int, *, block_d: int = 2048,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """Bulyan coordinate phase, fused.

    Args:
      selected: ``(theta, d)`` stack of the theta selected gradients.
      f: Byzantine bound; requires ``beta = theta - 2f >= 1``.
      block_d: VMEM tile width along d.
      interpret: ``None`` resolves per backend (compiled on TPU,
        interpreter elsewhere); see ``pairwise_gram.resolve_interpret``.

    Returns:
      ``(d,)`` float32: per coordinate, the mean of the beta sorted
      values closest to the median.

    VMEM per step ~ (theta + 1) * block_d * 4 bytes (slab + output row) plus
    the unrolled temporaries; with theta = 16, block_d = 2048 that is well
    under VMEM even with double buffering.
    """
    theta, d = selected.shape
    beta = theta - 2 * f
    if beta < 1:
        raise ValueError(f"need theta > 2f (theta={theta}, f={f})")
    block_d = min(block_d, max(d, 128))
    pad = (-d) % block_d
    if pad:
        selected = jnp.pad(selected, ((0, 0), (0, pad)))
    dp = selected.shape[1]
    out = pl.pallas_call(
        _make_kernel(theta, f),
        grid=(dp // block_d,),
        in_specs=[pl.BlockSpec((theta, block_d), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, block_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, dp), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(selected)
    return out[0, :d]
