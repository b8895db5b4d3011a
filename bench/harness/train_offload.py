"""Runs ``"kind": "train_offload"`` traffic: the robust train step
(``harness/train.py``) for a configuration whose float32 reference does
not fit the chip with its committee's gradients.

The program, the feed, the window, the trace and the comparison are
``train.py``'s.  Only the reference's committee differs: it keeps each
worker's float32 gradient in host memory and puts one leaf's stack on
the device at a time (``reference/committee_offload.py``), where
``committee.run_steps`` would hold all seven on the chip (at 307 M
parameters, 8.6 GB of gradients beside 3.7 GB of parameters and moments,
and the stack once more while it is built).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from harness import train as base


def reference_readings(cell, seed: int, pool_host: list,
                       dtype=jnp.float32, half_batch: bool = False) -> dict:
    """``train.reference_readings`` through the host-held committee."""
    from reference import committee, committee_offload

    with jax.default_matmul_precision("highest"):
        p0 = base.strip_tail(cell.params(seed))
        out = committee_offload.run_steps(
            cell.ref, cell.c, p0, pool_host[:base.CHECK_STEPS],
            f=cell.mix["f"], margin=cell.mix["attack_margin"],
            opt=cell.opt_hp, dtype=dtype, half_batch=half_batch)
        del p0
        p3 = out.pop("params")
        out["change_norms"] = committee.change_norms(
            p3, base.strip_tail(cell.params(seed)))
    return out


def run(name, cfg, mix, limits, seed, seconds, trace, devices, t_start,
        step_factory=None, hook=None) -> tuple:
    """``train.run`` with this module's reference readings."""
    saved = base.reference_readings
    base.reference_readings = reference_readings
    try:
        return base.run(name, cfg, mix, limits, seed, seconds, trace,
                        devices, t_start, step_factory)
    finally:
        base.reference_readings = saved
