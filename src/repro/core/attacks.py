"""Byzantine attacks.

The paper's attack (§3.2/§3.3): the omniscient adversary waits for the
n - f honest gradients, submits ``B(gamma) = mean(honest) + gamma * E`` with
``E`` a one-hot coordinate (finite p) or the all-ones vector (l-inf), and
chooses the largest ``gamma`` still *selected* by the aggregation rule.  The
paper estimates gamma_m "by a simple linear regression"; we instead run an
in-graph geometric-growth + bisection search against the actual rule, which
is exact up to tolerance and jit-compatible.

Beyond-paper attacks used as additional benchmark adversaries: ALIE
("A Little Is Enough", Baruch et al. 2019), IPM (inner-product manipulation,
Xie et al. 2019), sign-flip, mimic, random, zero.  The asynchronous
runtime adds two delay-exploiting adversaries — ``stale_replay`` and
``slow_drift`` — which additionally read ``prev`` (their own previous
bus submissions, threaded by the async step builders; see
``repro.dist.async_train`` and docs/async-runtime.md).  The reputation
runtime (``repro.agg.reputation``, docs/reputation.md) adds
``reputation_burn`` (build trust honestly, then spend it on sign-flipped
ascent — step-threaded like the delay attacks) and ``colluding_majority``
(f identical submissions a bounded distance off the honest mean — the
arbitrary-f adversary that defeats every quorum rule at f >= n/2).

All attacks have the signature::

    attack(honest: (n_h, d), f: int, key, **kw) -> (f, d)

and are registered in ``ATTACKS``.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import gars
from repro.core.types import AttackResult


# ---------------------------------------------------------------------------
# selection checkers
# ---------------------------------------------------------------------------

def make_selection_checker(gar_name: str, f: int) -> Callable:
    """Return ``check(full_grads) -> bool`` — True when at least one of the
    *last f rows* (the Byzantine submissions) carries weight in the rule's
    output.  Used by the gamma_m search."""
    gar = gars.get_gar(gar_name)

    def check(full_grads: jnp.ndarray) -> jnp.ndarray:
        res = gar(full_grads, f)
        return jnp.sum(res.selected[-f:]) > 0

    return check


# ---------------------------------------------------------------------------
# gamma_m search (the "linear regression" of §3.2, done properly)
# ---------------------------------------------------------------------------

def find_gamma_max(honest: jnp.ndarray, f: int, direction: jnp.ndarray,
                   check: Callable, gamma0: float = 1e-3,
                   n_grow: int = 26, n_bisect: int = 30) -> jnp.ndarray:
    """Largest gamma such that ``mean(honest) + gamma * direction`` is still
    selected by the rule (per ``check``).  Geometric growth to bracket, then
    bisection.  Fully in-graph (static iteration counts)."""
    mean = jnp.mean(honest, axis=0)

    def selected(gamma):
        byz = mean[None, :] + gamma * direction[None, :]
        full = jnp.concatenate([honest, jnp.repeat(byz, f, axis=0)], axis=0)
        return check(full)

    # growth phase: lo = largest gamma seen selected, hi = smallest gamma
    # seen rejected
    def grow_body(_, carry):
        lo, hi, g = carry
        sel = selected(g)
        lo = jnp.where(sel & (g > lo), g, lo)
        hi = jnp.where((~sel) & (g < hi), g, hi)
        return lo, hi, g * 2.0

    lo, hi, _ = jax.lax.fori_loop(
        0, n_grow, grow_body,
        (jnp.asarray(0.0, honest.dtype), jnp.asarray(jnp.inf, honest.dtype),
         jnp.asarray(gamma0, honest.dtype)))
    # if never rejected, the attack is unbounded within the probed range
    hi = jnp.where(jnp.isfinite(hi), hi, lo * 2.0 + gamma0)

    def bisect_body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        sel = selected(mid)
        return jnp.where(sel, mid, lo), jnp.where(sel, hi, mid)

    lo, hi = jax.lax.fori_loop(0, n_bisect, bisect_body, (lo, hi))
    return lo


def gamma_closed_form(rule: str, d: int, f: int, delta_bar: float,
                      p: int = 2) -> float:
    """The paper's §B approximations of gamma_m (order-of-magnitude only).

    Brute:        gamma_m ~ ((1 - 2^{-p/2}) d)^{1/p} * delta_bar
    Krum/GeoMed:  gamma_m ~ ((f+1)^{p/q} - 2^{-p/2})^{1/p} d^{1/p} * delta_bar
                  with q=2 for Krum, q=1 for GeoMed and b=0.
    """
    if rule == "brute":
        return float(((1.0 - 2.0 ** (-p / 2.0)) * d) ** (1.0 / p) * delta_bar)
    q = 2.0 if rule == "krum" else 1.0
    b = 0.0
    inner = ((f + 1.0 - b) / (2.0 - b)) ** (p / q) - 2.0 ** (-p / 2.0)
    return float(max(inner, 1e-9) ** (1.0 / p) * d ** (1.0 / p) * delta_bar)


# ---------------------------------------------------------------------------
# the paper's attacks
# ---------------------------------------------------------------------------

def _delta_bar(honest: jnp.ndarray) -> jnp.ndarray:
    """Paper §B.1: average folded std per coordinate, E|v_i - v_j| =
    2 sigma / sqrt(pi) for gaussian coordinates."""
    return 2.0 / jnp.sqrt(jnp.pi) * jnp.mean(jnp.std(honest, axis=0))


def _closed_rule(gar_name: str) -> str:
    """Normalize a GAR name to the rule family §B's closed forms cover:
    ``bulyan-<base>`` collapses to its base, anything without its own
    estimate falls back to krum's."""
    base = (gar_name.split("-", 1)[1] if gar_name.startswith("bulyan-")
            else gar_name)
    return base if base in ("krum", "geomed", "brute") else "krum"


def _closed_gamma(rule: str, d: int, f: int, db: jnp.ndarray, p: int = 2
                  ) -> jnp.ndarray:
    """Traced-friendly version of ``gamma_closed_form`` (§B.2/§B.3)."""
    rule = _closed_rule(rule)
    if rule == "brute":
        return ((1.0 - 2.0 ** (-p / 2.0)) * d) ** (1.0 / p) * db
    q = 2.0 if rule == "krum" else 1.0
    inner = jnp.maximum(((f + 1.0) / 2.0) ** (p / q) - 2.0 ** (-p / 2.0),
                        1e-9)
    return inner ** (1.0 / p) * d ** (1.0 / p) * db


def omniscient_lp(honest: jnp.ndarray, f: int, key=None, *,
                  coord=0, gamma=None,
                  gar_name: str = "krum", margin: float = 1.0,
                  step=None) -> jnp.ndarray:
    """§3.2: one poisoned coordinate, magnitude just inside the selection
    margin (gamma_m * margin).

    gamma: None -> exact in-graph growth+bisection search against the rule;
           "closed" -> the paper's §B closed-form estimate (cheap, 1 pass);
           float -> fixed.
    coord: int | "rotate" (coordinate step mod d — the adversary may pick a
           new coordinate each round) | "top" (the coordinate the honest
           mean considers most important, attacked *against* its sign).
    """
    d = honest.shape[1]
    mean = jnp.mean(honest, axis=0)
    sign = 1.0
    if coord == "rotate":
        c = (jnp.asarray(step, jnp.int32) if step is not None
             else jnp.zeros((), jnp.int32)) % d
    elif coord == "top":
        c = jnp.argmax(jnp.abs(mean))
        sign = -jnp.sign(mean[c])
    else:
        c = jnp.asarray(coord, jnp.int32)
    e = (jnp.zeros((d,), honest.dtype).at[c].set(1.0)) * sign
    if gamma is None:
        check = make_selection_checker(gar_name, f)
        g = find_gamma_max(honest, f, e, check) * margin
    elif gamma == "closed":
        g = _closed_gamma(gar_name, d, f, _delta_bar(honest)) * margin
    else:
        g = jnp.asarray(gamma, honest.dtype)
    byz = mean[None, :] + g * e[None, :]
    return jnp.repeat(byz, f, axis=0)


def omniscient_linf(honest: jnp.ndarray, f: int, key=None, *,
                    gamma=None, gar_name: str = "krum",
                    margin: float = 1.0, step=None,
                    direction: str = "ones") -> jnp.ndarray:
    """§3.3: poison *every* coordinate by gamma.  E = all-ones, or
    ``direction="anti"``: against the sign of the honest mean (the
    omniscient adversary's worst-case choice of the +-1 vector)."""
    d = honest.shape[1]
    mean = jnp.mean(honest, axis=0)
    if direction == "anti":
        e = -jnp.sign(mean)
        e = jnp.where(e == 0, 1.0, e).astype(honest.dtype)
    else:
        e = jnp.ones((d,), honest.dtype)
    if gamma is None:
        check = make_selection_checker(gar_name, f)
        g = find_gamma_max(honest, f, e, check) * margin
    elif gamma == "closed":
        # per-coordinate leeway ~ delta_bar (no sqrt(d) amplification: the
        # lp distance grows with every poisoned coordinate)
        g = _delta_bar(honest) * margin
    else:
        g = jnp.asarray(gamma, honest.dtype)
    byz = mean[None, :] + g * e[None, :]
    return jnp.repeat(byz, f, axis=0)


# ---------------------------------------------------------------------------
# beyond-paper attacks
# ---------------------------------------------------------------------------

def alie(honest: jnp.ndarray, f: int, key=None, *, z: Optional[float] = None
         ) -> jnp.ndarray:
    """"A Little Is Enough": shift every coordinate by z_max standard
    deviations — small enough to evade distance tests, coordinated enough to
    bias the aggregate."""
    n_h = honest.shape[0]
    n = n_h + f
    if z is None:
        # supporters needed for a (corrupted) majority
        s = (n // 2) + 1 - f
        phi = max(min((n - f - s) / float(n - f), 1.0 - 1e-6), 1e-6)
        z = float(jax.scipy.special.ndtri(phi))
    mu = jnp.mean(honest, axis=0)
    sd = jnp.std(honest, axis=0)
    byz = mu - z * sd
    return jnp.repeat(byz[None, :], f, axis=0)


def ipm(honest: jnp.ndarray, f: int, key=None, *, eps: float = 0.5
        ) -> jnp.ndarray:
    """Inner-product manipulation: submit -eps * mean(honest); flips the
    aggregate's inner product with the true gradient when selected."""
    byz = -eps * jnp.mean(honest, axis=0)
    return jnp.repeat(byz[None, :], f, axis=0)


def signflip(honest: jnp.ndarray, f: int, key=None, *, scale: float = 1.0
             ) -> jnp.ndarray:
    byz = -scale * jnp.mean(honest, axis=0)
    return jnp.repeat(byz[None, :], f, axis=0)


def random_noise(honest: jnp.ndarray, f: int, key, *, scale: float = 10.0
                 ) -> jnp.ndarray:
    d = honest.shape[1]
    return scale * jax.random.normal(key, (f, d), honest.dtype)


def zero(honest: jnp.ndarray, f: int, key=None) -> jnp.ndarray:
    return jnp.zeros((f, honest.shape[1]), honest.dtype)


def mimic(honest: jnp.ndarray, f: int, key=None, *, target: int = 0
          ) -> jnp.ndarray:
    """Copy one honest worker — starves diversity-dependent rules."""
    return jnp.repeat(honest[target][None, :], f, axis=0)


# ---------------------------------------------------------------------------
# delay-exploiting attacks (the asynchronous runtime's adversaries)
# ---------------------------------------------------------------------------
#
# Both read ``prev`` — the Byzantine rows of the previous GradientBus —
# which only the async step builders thread through (repro.dist.async_train
# / repro.training.trainer).  Called without ``prev`` (the synchronous
# runtime) they degenerate to a mimic-the-mean submission each step.

def stale_replay(honest: jnp.ndarray, f: int, key=None, *,
                 prev: Optional[jnp.ndarray] = None, step=None,
                 hold: int = 0, scale: float = 1.0) -> jnp.ndarray:
    """Replay a once-credible gradient forever (async ε-analogue, part 1).

    At step 0 the omniscient adversary records the honest mean — a
    perfectly legitimate submission — scaled by ``scale``, then resubmits
    it *unchanged* every step while stamping a fresh arrival on the bus.
    Under bounded staleness an old honest gradient is expected, so the
    replay hides in the leeway asynchrony opens; as honest training
    moves on, the frozen early-training direction keeps over-applying
    itself through the average (``scale`` amplifies the replayed
    magnitude, ``scale < 0`` replays the *ascent* direction — the
    classic poisoned-replay variants).  ``hold > 0`` re-records every
    ``hold`` steps (a replay window instead of a full freeze)."""
    mean = jnp.mean(honest, axis=0)
    rec = jnp.repeat(scale * mean[None, :], f, axis=0)
    if prev is None:
        return rec
    t = jnp.asarray(step if step is not None else 0, jnp.int32)
    refresh = t == 0
    if hold > 0:
        refresh = refresh | (t % hold == 0)
    return jnp.where(refresh, rec, prev.astype(rec.dtype)
                     ).astype(honest.dtype)


def slow_drift(honest: jnp.ndarray, f: int, key=None, *,
               prev: Optional[jnp.ndarray] = None, step=None,
               eps: float = 0.5, direction: str = "anti") -> jnp.ndarray:
    """Drift from the honest mean by eps * delta_bar per step (part 2).

    The async analogue of the paper's ε-perturbation: each submission
    differs from the adversary's *previous* one by less than the honest
    workers' own per-step spread (delta_bar, §B.1), so no single step is
    distinguishable from an honest straggler — but the drift integrates
    into an O(steps) displacement along ``direction`` ("anti": against
    the sign of the current honest mean; "ones": the all-ones vector)."""
    mean = jnp.mean(honest, axis=0)
    rec = jnp.repeat(mean[None, :], f, axis=0)
    if direction == "anti":
        e = -jnp.sign(mean)
        e = jnp.where(e == 0, 1.0, e).astype(honest.dtype)
    else:
        e = jnp.ones_like(mean)
    db = _delta_bar(honest)
    if prev is None:
        return rec + eps * db * e[None, :]
    t = jnp.asarray(step if step is not None else 0, jnp.int32)
    drifted = prev.astype(jnp.float32) + eps * db * e[None, :]
    return jnp.where(t == 0, rec, drifted).astype(honest.dtype)


# ---------------------------------------------------------------------------
# reputation attacks (the arbitrary-f runtime's adversaries)
# ---------------------------------------------------------------------------
#
# Adversaries of the ``reputation-*`` rules (repro.agg.reputation).  Both
# thread ``step`` like the delay attacks thread ``prev``; called without
# it they behave as their step-0 form.

def reputation_burn(honest: jnp.ndarray, f: int, key=None, *,
                    prev: Optional[jnp.ndarray] = None, step=None,
                    build: int = 5, scale: float = 3.0) -> jnp.ndarray:
    """Build trust honestly, then burn it (the reputation analogue of
    ``stale_replay``).

    For the first ``build`` steps the adversary submits the honest mean —
    a perfect-agreement submission that drives its reputation score to
    the maximum — then switches to ``-scale * mean``, spending the
    accumulated trust on sign-flipped ascent.  Against a reputation rule
    the EMA must *monotonically* burn the attacker's score back down
    after the flip (pinned by ``tests/test_reputation.py``); against a
    stateless rule the attack degenerates to delayed ``signflip``.
    ``prev`` is accepted for signature parity with the delay attacks but
    unused — the burn schedule is a pure function of ``step``."""
    del prev  # signature parity with the delay-exploiting attacks
    mean = jnp.mean(honest, axis=0)
    t = jnp.asarray(step if step is not None else 0, jnp.int32)
    byz = jnp.where(t < build, mean, -scale * mean)
    return jnp.repeat(byz[None, :], f, axis=0)


def colluding_majority(honest: jnp.ndarray, f: int, key=None, *,
                       eps: float = 4.0,
                       direction: str = "random") -> jnp.ndarray:
    """f identical colluders a bounded distance off the honest mean.

    The arbitrary-f adversary: all ``f`` Byzantine workers submit the
    *same* point ``mean + eps * delta_bar * u`` (``u`` a unit
    direction).  At ``f >= n/2`` the colluding cluster is the tightest
    neighborhood in the stack, so every distance-based selection rule
    whose quorum was (wrongly) declared satisfied picks a colluder, and
    coordinate-wise rules place the median inside the cluster — only
    auxiliary-batch reputation scoring (``AggSpec(aux_batch=...)``)
    recovers, since agreement with the clean gradient is the one signal
    the colluders cannot vote on.  ``eps`` scales the offset in units
    of the honest spread (§B.1 delta_bar), keeping each colluder
    individually plausible.

    ``direction`` picks ``u`` (mirroring ``omniscient_linf``):
    ``"random"`` draws a fresh unit vector from ``key`` — in high
    dimension nearly orthogonal to the honest mean, so the cluster
    drags the aggregate sideways; ``"anti"`` sets ``u = -mean/|mean|``,
    the descent-reversing worst case that cosine-based reputation
    scoring punishes hardest."""
    d = honest.shape[1]
    mean = jnp.mean(honest, axis=0)
    if direction == "anti":
        u = -(mean / (jnp.linalg.norm(mean) + 1e-12))
    elif direction == "random":
        if key is None:
            key = jax.random.PRNGKey(0)
        u = jax.random.normal(key, (d,), jnp.float32)
        u = (u / (jnp.linalg.norm(u) + 1e-12)).astype(honest.dtype)
    else:
        raise ValueError(
            f"colluding_majority direction must be 'random' or 'anti', "
            f"got {direction!r}")
    byz = mean + eps * _delta_bar(honest) * u
    return jnp.repeat(byz[None, :], f, axis=0)


ATTACKS = {
    "none": None,
    "omniscient_lp": omniscient_lp,
    "omniscient_linf": omniscient_linf,
    "alie": alie,
    "ipm": ipm,
    "signflip": signflip,
    "random": random_noise,
    "zero": zero,
    "mimic": mimic,
    "stale_replay": stale_replay,
    "slow_drift": slow_drift,
    "reputation_burn": reputation_burn,
    "colluding_majority": colluding_majority,
}

#: the delay-exploiting attacks: they read ``prev``, their own previous
#: bus rows, which the asynchronous step bodies hand them
DELAY_ATTACKS = ("stale_replay", "slow_drift")


def get_attack(name: str):
    if name not in ATTACKS:
        raise KeyError(f"unknown attack {name!r}; have {sorted(ATTACKS)}")
    return ATTACKS[name]
