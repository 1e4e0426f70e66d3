"""One caller, closed loop: ``plan.matvec(x)`` with ``done=None`` on an
Alg. 1 plan whose workers are placed over the chips, one chip at a time
slowed by a co-tenant.

Parameters (a cell's ``params``):
  batch          columns of x per call
  pool           x batches made at set-up and replicated on every chip;
                 each call takes one at random
  phase_calls    calls in a phase; each phase's slowed chip (or none) is
                 drawn in rounds over {none, chip 0, .., chip d-1}, each
                 round a seeded permutation
  cotenant_ms    device time of one co-tenant program, calibrated once
                 at set-up
  cotenant_size  side of the co-tenant's square float32 matrices
  sample         outputs kept per observed decode, drawn from the seed,
                 and compared with the float64 reference after the window

The co-tenant is another tenant's job on the slowed chip: a jitted loop
of dense matmuls on data held there (``jit_cotenant_load`` in a trace).
Before each call a new one is issued on the phase's slowed chip only if
the one before it there has finished, so at most one is in flight.

The program, not the traffic, decides which workers decode: each call's
spec records the decode rows and the chips launched that the executor
reports (``repro.runtime.placement.CallRecord``), and the check, the
sample and the roofline count follow them.  In a control run no program
runs; there the spec holds the phase's expected decode, every chip but
the slowed one (the first chips where none is slowed).
"""

from __future__ import annotations

import inspect
import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from bench import harness, operands, reference, schemes


@dataclass
class Spec:
    x: int                      # index into the pool
    slowed: int                 # the phase's slowed chip, -1: none
    rows: tuple = ()            # workers whose results decoded
    launched: tuple = ()        # chips launched on
    harvest_s: float | None = None


@jax.jit
def cotenant_load(w, iters):
    """Another tenant's job: ``iters`` dense matmuls on ``w``."""
    return jax.lax.fori_loop(0, iters, lambda _, y: jnp.tanh(y @ w), w)


class Cotenant:
    """At most one co-tenant program in flight on each chip."""

    def __init__(self, ws, iters):
        self.ws, self.iters = ws, iters
        self.inflight = [None] * len(ws)

    def load(self, chip: int) -> bool:
        """Issue one on ``chip`` unless the last one there is still
        running; True where one was issued."""
        prev = self.inflight[chip]
        if prev is not None and not prev.is_ready():
            return False
        self.inflight[chip] = cotenant_load(self.ws[chip], self.iters[chip])
        return True


class PhaseCycle:
    """The slowed chip of each phase of ``calls`` calls: -1 (none) or a
    chip, in rounds, each round a permutation drawn from the caller's
    generator."""

    def __init__(self, chips: int, calls: int):
        self.cycle = schemes.MaskCycle(chips + 1)
        self.calls, self.left, self.slowed = calls, 0, -1

    def draw(self, rng: np.random.Generator) -> int:
        if self.left == 0:
            self.slowed = self.cycle.draw(rng) - 1
            self.left = self.calls
        self.left -= 1
        return self.slowed


def expected_chips(slowed: int, chips: int, need: int) -> tuple[int, ...]:
    """The chips a decode takes where ``slowed`` finishes last."""
    order = [c for c in range(chips) if c != slowed] + [slowed] * (slowed >= 0)
    return tuple(sorted(order[:need]))


class Stream:
    kind = "mv"

    def __init__(self, config: dict, params: dict, seed: int, backend: str,
                 phase):
        from repro.api import compile_plan  # noqa: PLC0415

        if "devices" not in inspect.signature(compile_plan).parameters:
            # a program that cannot place workers fails before set-up
            raise TypeError("compile_plan takes no devices=: this program "
                            "cannot place its workers on several chips")
        t, r = config["A"]
        self.n, self.k = config["n"], config["k_A"]
        self.chips = config["chips"]
        self.per = self.n // self.chips
        self.need = -(-self.k // self.per)
        self.batch = params["batch"]
        self.sample_size = params["sample"]
        devices = jax.devices()[: self.chips]
        if len(devices) < self.chips:
            raise ValueError(f"{self.chips} chips needed, found "
                             f"{len(devices)}")
        everywhere = NamedSharding(Mesh(np.asarray(devices), ("chips",)),
                                   PartitionSpec())
        with phase("operands"):
            (self.A,), (pool,) = operands.make(
                operands.device_key(seed), shapes=((t, r),),
                zeros=config["zeros"],
                dense=((params["pool"], self.batch, t),))
            self.xs0 = [pool[i] for i in range(params["pool"])]
            self.xs = [jax.device_put(x, everywhere) for x in self.xs0]
            jax.block_until_ready(self.xs)
        with phase("compile_plan"):
            self.plan = compile_plan(self.A, scheme=config["scheme"],
                                     n=self.n, k_A=self.k,
                                     seed=config["coefficient_seed"],
                                     backend=backend, devices=devices)
        with phase("warm_chips"):
            self.plan.executor.warm(self.xs[0])
        with phase("cotenant"):
            self.cotenant = self._cotenant(devices, params, seed)
        self.supports = schemes.alg1_supports(self.n, self.k,
                                              config["omega_A"])
        self.G = schemes.mv_system(self.n, self.k, config["omega_A"],
                                   config["coefficient_seed"])
        self.phases = PhaseCycle(self.chips, params["phase_calls"])

    def _cotenant(self, devices, params, seed) -> Cotenant:
        """The co-tenant's data on each chip, its loop compiled there,
        and the loop count that takes ``cotenant_ms`` of device time."""
        size = params["cotenant_size"]
        w = jax.random.normal(operands.device_key(seed, 5), (size, size),
                              jnp.float32) / np.sqrt(size)
        ws = [jax.device_put(w, d) for d in devices]

        def seconds(iters: int) -> float:
            n = jax.device_put(np.int32(iters), devices[0])
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                cotenant_load(ws[0], n).block_until_ready()
                best = min(best, time.perf_counter() - t0)
            return best

        seconds(1)                                  # compile
        base = 32
        per_iter = max((seconds(2 * base) - seconds(base)) / base, 1e-7)
        iters = max(int(round(params["cotenant_ms"] * 1e-3 / per_iter)), 1)
        counts = [jax.device_put(np.int32(iters), d) for d in devices]
        jax.block_until_ready([cotenant_load(x, n)
                               for x, n in zip(ws, counts)])
        harness.info(cotenant={"iters": iters,
                               "ms_per_program": 1e3 * seconds(iters)})
        return Cotenant(ws, counts)

    # -- the timed path --------------------------------------------------

    def warm_specs(self) -> list[Spec]:
        return [Spec(0, s, *self._expected(s))
                for s in range(-1, self.chips)]

    def _expected(self, slowed: int) -> tuple[tuple, tuple]:
        chips = expected_chips(slowed, self.chips, self.need)
        mask = np.zeros(self.n, bool)
        for c in chips:
            mask[c * self.per:(c + 1) * self.per] = True
        return tuple(schemes.fastest_k(mask, self.k)), chips

    def draw(self, rng: np.random.Generator) -> Spec:
        slowed = self.phases.draw(rng)
        if slowed >= 0:
            self.cotenant.load(slowed)
        return Spec(int(rng.integers(len(self.xs))), slowed,
                    *self._expected(slowed))

    def call(self, spec: Spec):
        y = self.plan.matvec(self.xs[spec.x])
        rec = self.plan.executor.records[-1]
        spec.rows, spec.launched = rec.rows, rec.launched
        spec.harvest_s = rec.harvest_s
        return y

    def kept(self, spec, y):
        """What the check keeps of an output: all of it."""
        return y

    def reference_call(self, spec, precision: str):
        """The reference in the program's place, on chip 0, in a lower
        ``precision`` (see ``reference.lower_precision_dot``)."""
        return reference.lower_precision_dot(self.xs0[spec.x], self.A,
                                             precision)

    # -- what the run checks and counts ----------------------------------

    def scheme_matches(self) -> bool:
        sch = self.plan.scheme
        return (sch.n, sch.k_A) == (self.n, self.k) and \
            [tuple(s) for s in sch.supports] == self.supports and \
            np.array_equal(np.asarray(self.plan.G, np.float64), self.G)

    def free_program(self) -> None:
        self.plan = None

    def decoded_chips(self, spec) -> tuple[int, ...]:
        return tuple(sorted({i // self.per for i in spec.rows}))

    def errors(self, samples) -> list[tuple[str, float, float]]:
        """(decoded chips, error, decode condition number) of each kept
        output, the error against ``A^T x`` in float64."""
        a_csr = reference.sparse64(self.A)
        need = sorted({spec.x for spec, _ in samples})
        refs = {x: reference.mv_expected(a_csr, self.xs0[x]) for x in need}
        out = []
        for spec, y in samples:
            done = np.zeros(self.n, bool)
            done[list(spec.rows)] = True
            out.append((f"chips {self.decoded_chips(spec)}",
                        reference.rel_err(y, refs[spec.x]),
                        schemes.decode_kappa(self.G, done)))
        return out

    def work_key(self, spec) -> tuple:
        """Calls with one key do the same work: the decode that ran and
        the chips launched.  The check keeps a sample of each key's
        outputs."""
        return self.decoded_chips(spec), tuple(spec.launched)

    def describe(self, key) -> dict:
        """The work of a call with ``key``, for the roofline counts:
        every worker of every chip launched."""
        _, launched = key
        return {"A_nonzero": self.A != 0, "k_A": self.k,
                "supports_A": self.supports,
                "selected": [c * self.per + i for c in launched
                             for i in range(self.per)],
                "width": self.batch}
