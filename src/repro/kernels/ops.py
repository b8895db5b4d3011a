"""Public jit'd entry points for the Pallas kernels.

``use_pallas`` toggles between the kernel (interpret-mode on CPU, compiled
on TPU) and the pure-jnp oracle.  The GAR core calls these through
``repro.kernels.ops`` so a single flag flips the whole framework.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.bulyan_select import bulyan_select as _bulyan_select
from repro.kernels.common import resolve_interpret
from repro.kernels.pairwise_gram import pairwise_gram as _pairwise_gram

__all__ = ["bulyan_coordinate", "pairwise_distances"]

# ``use_pallas=None`` takes the kernel where it runs compiled and the
# oracle where it would run in the pure-Python Pallas interpreter, asked
# when called: asking at import would initialise a backend.


def pairwise_distances(grads: jnp.ndarray, *,
                       use_pallas: Optional[bool] = None,
                       block_d: int = 4096) -> jnp.ndarray:
    """Squared pairwise distances; kernel or oracle.

    Args:
      grads: ``(n, d)`` worker-stacked flat gradients.
      use_pallas: ``None`` picks the kernel on TPU and the jnp oracle
        elsewhere; ``True`` forces the kernel (interpreter off-TPU).
      block_d: kernel VMEM tile width.

    Returns:
      ``(n, n)`` float32 squared distances, zero diagonal.
    """
    if use_pallas is None:
        use_pallas = not resolve_interpret(None)
    if use_pallas:
        return _pairwise_gram(grads, block_d=block_d)
    return ref.pairwise_gram_ref(grads)


def bulyan_coordinate(selected: jnp.ndarray, f: int, *,
                      use_pallas: Optional[bool] = None,
                      block_d: int = 2048) -> jnp.ndarray:
    """Bulyan coordinate phase; kernel or oracle.

    Args:
      selected: ``(theta, d)`` selected-gradient stack.
      f: Byzantine bound (``beta = theta - 2f``).
      use_pallas: ``None`` picks the kernel on TPU, the pure-jnp
        ``repro.core.bulyan.coordinate_phase`` elsewhere.
      block_d: kernel VMEM tile width.

    Returns:
      ``(d,)`` float32 coordinate-phase aggregate.
    """
    if use_pallas is None:
        use_pallas = not resolve_interpret(None)
    if use_pallas:
        return _bulyan_select(selected, f, block_d=block_d)
    from repro.core.bulyan import coordinate_phase
    return coordinate_phase(selected, f)
