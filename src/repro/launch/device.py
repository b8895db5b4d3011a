"""Device facts and process set-up shared by the entry points.

Two things every entry point needs and none should re-derive:

  peaks                 the published per-chip peak rates, one table keyed
                        by ``jax.Device.device_kind``; a kind that is not
                        in the table raises instead of borrowing another
                        chip's numbers
  enable_compile_cache  JAX's persistent compilation cache: wherever
                        ``JAX_COMPILATION_CACHE_DIR`` says, else one fixed
                        directory inside the checkout

Importing this module never touches jax device state.
"""
from __future__ import annotations

import os
import pathlib
from typing import NamedTuple

__all__ = ["CACHE_DIR", "PRODUCTION_KIND", "Peaks", "enable_compile_cache",
           "peaks"]

#: the checkout's own cache directory (git-ignored); a fixed path, since
#: the directory is part of the cache key
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


class Peaks(NamedTuple):
    """Published peak rates of one chip."""

    flops: float        # bf16 FLOP/s
    hbm_bw: float       # HBM bytes/s
    ici_link_bw: float  # bytes/s per inter-chip link


#: source of every row: Google Cloud documentation, "TPU v5e" — 197
#: TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of inter-chip
#: interconnect over 4 links
_PEAKS = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9,
                         ici_link_bw=1600e9 / 8 / 4),
}


#: device kind of the production meshes (v5e pods) that the dry-run and
#: the analytic roofline rows model
PRODUCTION_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> Peaks:
    """Peak rates of a chip, by its ``device_kind``.

    Args:
      device_kind: ``jax.devices()[0].device_kind``, e.g. ``"TPU v5 lite"``.

    Returns:
      The chip's :class:`Peaks`.

    Raises:
      KeyError: the kind has no published peaks in the table.
    """
    try:
        return _PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {sorted(_PEAKS)}") from None


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed; otherwise the cache goes to :data:`CACHE_DIR`.
    Call before the first compile.

    Returns:
      The cache directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
