"""The paper's codes (arXiv:2408.05152, Alg. 1 and Alg. 2), as stated.

The yardstick's own copy of the support sets and of the random
coefficients on them.  The roofline counts read which source
block-columns each coded block combines from here; the check reads the
condition number of each straggler mask's decode from here; and a run
checks that the plan it drives uses the same sets and coefficients.
"""

from __future__ import annotations

import numpy as np


def alg1_supports(n: int, k_a: int, omega_a: int) -> list[tuple[int, ...]]:
    """Alg. 1: worker i < k_A combines {i, ..., i+omega_A-1}; worker
    i >= k_A combines {i*omega_A, ..., (i+1)*omega_A-1}; all mod k_A."""
    return [tuple(((i if i < k_a else i * omega_a) + j) % k_a
                  for j in range(omega_a)) for i in range(n)]


def alg2_supports(n: int, k_a: int, k_b: int, omega_a: int, omega_b: int
                  ) -> tuple[list[tuple[int, ...]], list[tuple[int, ...]]]:
    """Alg. 2: worker i < k_A k_B combines A-blocks {i, ..,
    i+omega_A-1} mod k_A and B-blocks {j, .., j+omega_B-1} mod k_B with
    j = i // k_A; a worker i beyond them combines A-blocks from
    (i mod k_A) * omega_A and B-blocks from (i*omega_A // k_A) * omega_B."""
    sup_a, sup_b = [], []
    for i in range(n):
        if i < k_a * k_b:
            a0, b0 = i, i // k_a
        else:
            a0 = (i % k_a) * omega_a
            b0 = ((i * omega_a) // k_a) * omega_b
        sup_a.append(tuple((a0 + j) % k_a for j in range(omega_a)))
        sup_b.append(tuple((b0 + j) % k_b for j in range(omega_b)))
    return sup_a, sup_b


def fastest_k(done, k: int) -> list[int]:
    """The k workers that decode a call: the first k that finished, in
    worker order (the mask says only who finished)."""
    rows = [i for i, ok in enumerate(done) if ok][:k]
    if len(rows) < k:
        raise ValueError(f"only {len(rows)} workers done, need {k}")
    return rows


def straggler_masks(n: int, stragglers: int, patterns: int, all_alive: bool,
                    rng: np.random.Generator) -> list[np.ndarray]:
    """``patterns`` distinct masks with ``stragglers`` workers off, and
    the all-alive mask first where asked."""
    masks = [np.ones(n, bool)] if all_alive else []
    seen = set()
    while len(seen) < patterns:
        off = tuple(sorted(rng.choice(n, stragglers, replace=False).tolist()))
        if off in seen:
            continue
        seen.add(off)
        mask = np.ones(n, bool)
        mask[list(off)] = False
        masks.append(mask)
    return masks


class MaskCycle:
    """Hands out mask indices in rounds, each round a permutation of all
    of them drawn from the caller's generator: every mask is called
    equally often, in another order for every seed."""

    def __init__(self, count: int):
        self.count = count
        self._round: list[int] = []

    def draw(self, rng: np.random.Generator) -> int:
        if not self._round:
            self._round = rng.permutation(self.count).tolist()
        return int(self._round.pop())


def coefficients(supports, k: int, rng: np.random.Generator) -> np.ndarray:
    """(len(supports), k): uniform(-1, 1) on each support, 0 elsewhere,
    drawn row by row."""
    r = np.zeros((len(supports), k))
    for i, sup in enumerate(supports):
        r[i, list(sup)] = rng.uniform(-1.0, 1.0, size=len(sup))
    return r


def mv_system(n: int, k_a: int, omega_a: int, seed: int) -> np.ndarray:
    """Alg. 1's (n, k_A) system matrix for coefficient seed ``seed``."""
    return coefficients(alg1_supports(n, k_a, omega_a), k_a,
                        np.random.default_rng(seed))


def mm_system(n: int, k_a: int, k_b: int, omega_a: int, omega_b: int,
              seed: int) -> np.ndarray:
    """Alg. 2's (n, k_A k_B) system matrix: row i is kron(Ra[i], Rb[i])
    over the unknowns A_q^T B_p, flattened as q * k_B + p."""
    sup_a, sup_b = alg2_supports(n, k_a, k_b, omega_a, omega_b)
    rng = np.random.default_rng(seed)
    ra = coefficients(sup_a, k_a, rng)
    rb = coefficients(sup_b, k_b, rng)
    return (ra[:, :, None] * rb[:, None, :]).reshape(n, -1)


def decode_kappa(G: np.ndarray, done) -> float:
    """2-norm condition number of the decode under mask ``done``: the
    factor by which it can amplify the workers' rounding."""
    return float(np.linalg.cond(G[fastest_k(done, G.shape[1])]))
