"""Expected data-access cost E[DAC] (paper §III-D, Lemmas III.2 / III.3).

The closed forms assume the predicted position lands at a uniformly
distributed in-page offset.  ``*_exact`` variants evaluate the finite sums in
the lemma proofs directly (used by property tests to certify the closed
forms), and the RMI variant computes the workload-weighted leaf mixture of
§V-C.

The RMI mixture is host NumPy float64, not ``jnp``: its inputs (leaf error
bounds and routing counts) are host arrays, and its one caller wants a
Python float, so a device version only adds a put of each array and a
blocking read of one scalar per candidate, and rounds the sum to float32.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

__all__ = [
    "expected_dac_all_at_once",
    "expected_dac_one_by_one",
    "expected_dac",
    "expected_dac_all_at_once_exact",
    "expected_dac_one_by_one_exact",
    "expected_dac_rmi",
]

STRATEGIES = ("all_at_once", "one_by_one")


def expected_dac_all_at_once(eps, c_ipp):
    """Lemma III.2:  E[DAC] = 1 + 2*eps / C_ipp   (S2 fetching)."""
    return 1.0 + 2.0 * jnp.asarray(eps, jnp.float32) / jnp.asarray(c_ipp, jnp.float32)


def expected_dac_one_by_one(eps, c_ipp):
    """Lemma III.3:  E[DAC] = 1 + eps / C_ipp   (S1 fetching)."""
    return 1.0 + jnp.asarray(eps, jnp.float32) / jnp.asarray(c_ipp, jnp.float32)


def expected_dac(eps, c_ipp, strategy: str = "all_at_once"):
    if strategy == "all_at_once":
        return expected_dac_all_at_once(eps, c_ipp)
    if strategy == "one_by_one":
        return expected_dac_one_by_one(eps, c_ipp)
    raise ValueError(f"unknown strategy {strategy!r}; expected one of {STRATEGIES}")


# ---------------------------------------------------------------------------
# Exact finite sums from the lemma proofs (test oracles)
# ---------------------------------------------------------------------------

def expected_dac_all_at_once_exact(eps: int, c_ipp: int) -> float:
    """Direct evaluation of the sum in the proof of Lemma III.2."""
    s = np.arange(c_ipp)
    total = 1.0 + np.ceil((eps - s) / c_ipp).clip(min=0)
    total += np.ceil((eps - (c_ipp - 1 - s)) / c_ipp).clip(min=0)
    return float(total.mean())


def expected_dac_one_by_one_exact(eps: int, c_ipp: int) -> float:
    """Direct evaluation of the double sum in the proof of Lemma III.3."""
    x = np.arange(2 * eps + 1)[:, None]
    k = np.arange(c_ipp)[None, :]
    extra = (k + x) // c_ipp
    return float(1.0 + extra.mean())


# ---------------------------------------------------------------------------
# RMI mixture (§V-C): E[DAC] = sum_j w_j (1 + lambda * eps_j / C_ipp)
# ---------------------------------------------------------------------------

def expected_dac_rmi(leaf_eps, leaf_weights, c_ipp,
                     strategy: str = "all_at_once") -> float:
    """Workload-weighted mixture over leaf-local error bounds.

    ``leaf_eps[j]`` is the empirical max error of leaf j, ``leaf_weights[j]``
    how many of the workload's queries route to leaf j (any scale: the
    weights are normalised to probabilities here).  Evaluated in NumPy float64 on the host and returned as a Python float:
    it does no device work.
    """
    lam = 2.0 if strategy == "all_at_once" else 1.0
    leaf_eps = np.asarray(leaf_eps, np.float64)
    w = np.asarray(leaf_weights, np.float64)
    w = w / max(float(w.sum()), 1e-30)
    return float(np.sum(w * (1.0 + lam * leaf_eps / float(c_ipp))))
