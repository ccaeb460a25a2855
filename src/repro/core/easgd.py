"""Elastic Averaging SGD family — paper §3.3, §5.1 (eqs. 1, 2, 5, 6).

The rules (paper notation; η learning rate, ρ elastic strength, μ momentum):

  worker  (eq 1):  W⁽ⁱ⁾ ← W⁽ⁱ⁾ − η·(ΔW⁽ⁱ⁾ + ρ·(W⁽ⁱ⁾ − W̄))
  center  (eq 2):  W̄    ← W̄ + η·ρ·Σᵢ (W⁽ⁱ⁾ − W̄)
  MEASGD  (eq 5):  V⁽ⁱ⁾ ← μ·V⁽ⁱ⁾ − η·ΔW⁽ⁱ⁾
  MEASGD  (eq 6):  W⁽ⁱ⁾ ← W⁽ⁱ⁾ + V⁽ⁱ⁾ − η·ρ·(W⁽ⁱ⁾ − W̄)

All functions below are pure, operate on pytrees, and are shared by
 * the synchronous multi-pod runtime (``core.elastic`` — Sync EASGD),
 * the asynchronous engine (``core.async_engine`` — Original / Async /
   Hogwild EASGD and their SGD counterparts), and
 * the unit/property tests (the oracle is this module run on scalars).

Identities used as test invariants:
 * ρ = 0   → eq 1 degenerates to plain SGD, eq 5–6 to momentum SGD.
 * 1 worker, ρ>0 → worker and center contract toward each other; the
   average (W + W̄)/2 follows plain SGD up to O(ηρ)².
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from repro.utils.pytree import tree_map


@dataclasses.dataclass(frozen=True)
class EASGDConfig:
    """Hyper-parameters of the elastic-averaging family.

    tau: communication period — workers exchange with the center every
    ``tau`` local steps (paper uses τ=1; EASGD supports τ≥1, and τ is the
    natural cross-pod bandwidth lever at 1000+ nodes).
    """

    eta: float = 0.01          # learning rate η
    rho: float = 0.01          # elastic strength ρ  (β = η·ρ·P in EASGD paper)
    mu: float = 0.9            # momentum μ (MEASGD only)
    tau: int = 1               # communication period
    nesterov: bool = False

    @property
    def alpha(self) -> float:
        """Elastic step size α = η·ρ (the EASGD paper's notation)."""
        return self.eta * self.rho


# ---------------------------------------------------------------------------
# worker-side updates
# ---------------------------------------------------------------------------

def sgd_update(w, grad, cfg: EASGDConfig):
    """Plain SGD: W ← W − η·ΔW (the ρ=0 degenerate case of eq 1)."""
    return tree_map(lambda w_, g_: w_ - cfg.eta * g_.astype(w_.dtype), w, grad)


def msgd_update(w, v, grad, cfg: EASGDConfig):
    """Momentum SGD (eqs 3–4): V ← μV − ηΔW;  W ← W + V."""
    v_new = tree_map(
        lambda v_, g_: cfg.mu * v_ - cfg.eta * g_.astype(v_.dtype), v, grad
    )
    if cfg.nesterov:
        w_new = tree_map(
            lambda w_, v_, g_: w_ + cfg.mu * v_ - cfg.eta * g_.astype(w_.dtype),
            w, v_new, grad,
        )
    else:
        w_new = tree_map(lambda w_, v_: w_ + v_.astype(w_.dtype), w, v_new)
    return w_new, v_new


def easgd_worker_update(w, grad, center, cfg: EASGDConfig):
    """Eq 1: W ← W − η(ΔW + ρ(W − W̄))."""
    return tree_map(
        lambda w_, g_, c_: w_
        - cfg.eta * (g_.astype(w_.dtype) + cfg.rho * (w_ - c_.astype(w_.dtype))),
        w, grad, center,
    )


def measgd_worker_update(w, v, grad, center, cfg: EASGDConfig):
    """Eqs 5–6: V ← μV − ηΔW;  W ← W + V − ηρ(W − W̄)."""
    v_new = tree_map(
        lambda v_, g_: cfg.mu * v_ - cfg.eta * g_.astype(v_.dtype), v, grad
    )
    w_new = tree_map(
        lambda w_, v_, c_: w_
        + v_.astype(w_.dtype)
        - cfg.eta * cfg.rho * (w_ - c_.astype(w_.dtype)),
        w, v_new, center,
    )
    return w_new, v_new


# ---------------------------------------------------------------------------
# center-side updates
# ---------------------------------------------------------------------------

def center_update_from_sum(center, sum_w, n_workers: int, cfg: EASGDConfig):
    """Eq 2 given Σᵢ W⁽ⁱ⁾:  W̄ ← W̄ + ηρ (Σᵢ W⁽ⁱ⁾ − P·W̄)."""
    a = cfg.alpha
    return tree_map(
        lambda c_, s_: c_ + a * (s_.astype(c_.dtype) - n_workers * c_),
        center, sum_w,
    )


def center_update_from_mean(center, mean_w, n_workers: int, cfg: EASGDConfig):
    """Eq 2 given meanᵢ W⁽ⁱ⁾ (the form the packed cross-pod collective emits).

    W̄ ← W̄ + ηρP·(mean − W̄)  ≡  W̄ + ηρ Σᵢ(W⁽ⁱ⁾ − W̄).
    """
    a = cfg.alpha * n_workers
    return tree_map(
        lambda c_, m_: c_ + a * (m_.astype(c_.dtype) - c_), center, mean_w
    )


def center_update_single(center, w_i, cfg: EASGDConfig):
    """Round-robin / async form: one worker at a time (paper Alg. 1 line 14):
    W̄ ← W̄ + ηρ (W⁽ⁱ⁾ − W̄).
    """
    a = cfg.alpha
    return tree_map(
        lambda c_, w_: c_ + a * (w_.astype(c_.dtype) - c_), center, w_i
    )
