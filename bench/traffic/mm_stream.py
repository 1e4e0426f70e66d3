"""One caller, closed loop: ``plan.matmat(B, done)`` on an Alg. 2 plan.

Parameters (a cell's ``params``):
  patterns    straggler patterns fixed at set-up, each of ``stragglers``
              workers; calls take them in rounds, each round in a seeded
              order
  sample      outputs kept per mask, drawn from the seed, and compared
              with the float64 reference after the window
  columns     output columns the check keeps of each kept output, per
              block-column of B, drawn from the seed at set-up: every
              one of the k_A x k_B decoded blocks is compared on all of
              its rows

B is made once at set-up, so every call computes the same ``A^T B``
under another mask.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import operands, reference, schemes


class Stream:
    kind = "mm"

    def __init__(self, config: dict, params: dict, seed: int, backend: str,
                 phase):
        from repro.api import compile_plan  # noqa: PLC0415

        (t, ra), (_, rb) = config["A"], config["B"]
        self.n, self.k_a, self.k_b = config["n"], config["k_A"], config["k_B"]
        self.k = self.k_a * self.k_b
        self.rb = rb
        self.sample_size = params["sample"]
        with phase("operands"):
            (self.A, self.B), _ = operands.make(
                operands.device_key(seed), shapes=((t, ra), (t, rb)),
                zeros=config["zeros"])
            jax.block_until_ready((self.A, self.B))
        with phase("compile_plan"):
            self.plan = compile_plan(self.A, scheme=config["scheme"],
                                     n=self.n, k_A=self.k_a, k_B=self.k_b,
                                     seed=config["coefficient_seed"],
                                     backend=backend)
        self.supports_a, self.supports_b = schemes.alg2_supports(
            self.n, self.k_a, self.k_b, config["omega_A"], config["omega_B"])
        self.G = schemes.mm_system(self.n, self.k_a, self.k_b,
                                   config["omega_A"], config["omega_B"],
                                   config["coefficient_seed"])
        self.masks = schemes.straggler_masks(
            self.n, params["stragglers"], params["patterns"],
            params.get("all_alive", False), np.random.default_rng([seed, 1]))
        self.cycle = schemes.MaskCycle(len(self.masks))
        self.cols = self.columns(params["columns"],
                                 np.random.default_rng([seed, 4]))
        self._cols = jnp.asarray(self.cols)

    def columns(self, per_block: int, rng: np.random.Generator) -> np.ndarray:
        """``per_block`` distinct output columns in each block-column of
        B (fewer where a block is narrower), sorted."""
        cb = -(-self.rb // self.k_b)
        picks = []
        for p in range(self.k_b):
            lo, hi = p * cb, min((p + 1) * cb, self.rb)
            if hi > lo:
                picks.append(lo + rng.choice(hi - lo, min(per_block, hi - lo),
                                             replace=False))
        return np.sort(np.concatenate(picks))

    # -- the timed path --------------------------------------------------

    def warm_specs(self) -> list[int]:
        return list(range(len(self.masks)))

    def draw(self, rng: np.random.Generator) -> int:
        return self.cycle.draw(rng)

    def call(self, spec):
        return self.plan.matmat(self.B, self.masks[spec])

    def kept(self, spec, y):
        """What the check keeps of an output: its sampled columns."""
        return y[:, self._cols]

    def reference_call(self, spec, precision: str):
        """The reference in the program's place, on the device, in a
        lower ``precision`` (see ``reference.lower_precision_dot``)."""
        return reference.lower_precision_dot(self.A.T, self.B, precision)

    # -- what the run checks and counts ----------------------------------

    def scheme_matches(self) -> bool:
        sch = self.plan.scheme
        return (sch.n, sch.k_A, sch.k_B) == (self.n, self.k_a, self.k_b) \
            and [tuple(s) for s in sch.supports_A] == self.supports_a \
            and [tuple(s) for s in sch.supports_B] == self.supports_b \
            and np.array_equal(np.asarray(self.plan.G, np.float64), self.G)

    def free_program(self) -> None:
        self.plan = None

    def errors(self, samples) -> list[tuple[int, float, float]]:
        """(mask, error, decode condition number) of each kept output,
        the error against the same columns of ``A^T B`` in float64."""
        if not samples:
            return []
        ref = reference.mm_expected(reference.sparse64(self.A),
                                    self.B[:, self._cols])
        return [(spec, reference.rel_err(y, ref),
                 schemes.decode_kappa(self.G, self.masks[spec]))
                for spec, y in samples]

    def work_key(self, spec) -> int:
        """Calls with one key do the same work: the mask decides it.  The
        check keeps a sample of each key's outputs."""
        return spec

    def describe(self, key: int) -> dict:
        """The work of a call with ``key``, for the roofline counts."""
        return {
            "A_nonzero": self.A != 0, "k_A": self.k_a,
            "supports_A": self.supports_a,
            "selected": schemes.fastest_k(self.masks[key], self.k),
            "width": -(-self.rb // self.k_b),
            "B_nonzero": self.B != 0, "k_B": self.k_b,
            "supports_B": self.supports_b,
        }
