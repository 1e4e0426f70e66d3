"""One caller, closed loop: ``plan.matvec(x, done)`` on an Alg. 1 plan.

Parameters (a cell's ``params``):
  batch       columns of x per call
  pool        x batches made at set-up; each call takes one at random
  patterns    straggler patterns fixed at set-up, each of ``stragglers``
              workers; with ``all_alive`` the all-alive mask is one more.
              Calls take the masks in rounds, each round in a seeded order
  sample      outputs kept per mask, drawn from the seed, and compared
              with the float64 reference after the window
"""

from __future__ import annotations

import jax
import numpy as np

from bench import operands, reference, schemes


class Stream:
    kind = "mv"

    def __init__(self, config: dict, params: dict, seed: int, backend: str,
                 phase):
        from repro.api import compile_plan  # noqa: PLC0415

        t, r = config["A"]
        self.n, self.k = config["n"], config["k_A"]
        self.batch, self.t = params["batch"], t
        self.sample_size = params["sample"]
        with phase("operands"):
            (self.A,), (pool,) = operands.make(
                operands.device_key(seed), shapes=((t, r),),
                zeros=config["zeros"],
                dense=((params["pool"], self.batch, t),))
            self.xs = [pool[i] for i in range(params["pool"])]
            jax.block_until_ready(self.xs)
        with phase("compile_plan"):
            self.plan = compile_plan(self.A, scheme=config["scheme"],
                                     n=self.n, k_A=self.k,
                                     seed=config["coefficient_seed"],
                                     backend=backend)
        self.supports = schemes.alg1_supports(self.n, self.k,
                                              config["omega_A"])
        self.G = schemes.mv_system(self.n, self.k, config["omega_A"],
                                   config["coefficient_seed"])
        self.masks = schemes.straggler_masks(
            self.n, params["stragglers"], params["patterns"],
            params.get("all_alive", False), np.random.default_rng([seed, 1]))
        self.cycle = schemes.MaskCycle(len(self.masks))

    # -- the timed path --------------------------------------------------

    def warm_specs(self) -> list[tuple[int, int]]:
        return [(0, m) for m in range(len(self.masks))]

    def draw(self, rng: np.random.Generator) -> tuple[int, int]:
        return int(rng.integers(len(self.xs))), self.cycle.draw(rng)

    def call(self, spec):
        x, m = spec
        return self.plan.matvec(self.xs[x], self.masks[m])

    def kept(self, spec, y):
        """What the check keeps of an output: all of it."""
        return y

    def reference_call(self, spec, precision: str):
        """The reference in the program's place, on the device, in a
        lower ``precision`` (see ``reference.lower_precision_dot``)."""
        return reference.lower_precision_dot(self.xs[spec[0]], self.A,
                                             precision)

    # -- what the run checks and counts ----------------------------------

    def scheme_matches(self) -> bool:
        sch = self.plan.scheme
        return (sch.n, sch.k_A) == (self.n, self.k) and \
            [tuple(s) for s in sch.supports] == self.supports and \
            np.array_equal(np.asarray(self.plan.G, np.float64), self.G)

    def free_program(self) -> None:
        self.plan = None

    def errors(self, samples) -> list[tuple[int, float, float]]:
        """(mask, error, decode condition number) of each kept output,
        the error against ``A^T x`` in float64."""
        a_csr = reference.sparse64(self.A)
        need = sorted({spec[0] for spec, _ in samples})
        refs = {x: reference.mv_expected(a_csr, self.xs[x]) for x in need}
        return [(spec[1], reference.rel_err(y, refs[spec[0]]),
                 schemes.decode_kappa(self.G, self.masks[spec[1]]))
                for spec, y in samples]

    def work_key(self, spec) -> int:
        """Calls with one key do the same work: the mask decides it.  The
        check keeps a sample of each key's outputs."""
        return spec[1]

    def describe(self, key: int) -> dict:
        """The work of a call with ``key``, for the roofline counts."""
        return {"A_nonzero": self.A != 0, "k_A": self.k,
                "supports_A": self.supports,
                "selected": schemes.fastest_k(self.masks[key], self.k),
                "width": self.batch}
