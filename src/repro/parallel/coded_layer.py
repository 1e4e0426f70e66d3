"""The paper's technique as a first-class framework feature: coded
linear layers with straggler resilience on a device mesh.

A ``CodedLinear`` wraps a logical (d_in, d_out) weight matrix.  At
build time the d_out block-columns are encoded per Alg. 1 into n coded
shards of width d_out/k; at apply time each "worker" (mesh slice or
vmap lane) computes its coded product, and the output is decoded from
the fastest k workers indicated by a runtime ``done`` mask -- one
compiled executable serves every straggler pattern.

Execution modes:
  * ``vmap``      -- virtual workers on one device (tests, edge sim).
  * ``shard_map`` -- workers = 'model'-axis mesh slices; each device
    holds ONLY its coded shard (1/k-th of the weight + omega/k overhead)
    and computes its product locally; decode happens after an
    all-gather of the n partial results (k x k solve, negligible).

All hot methods route through a compiled ``repro.api.CodedPlan`` (built
once by ``build`` via ``compile_plan``): the sparse backends (``packed``
/ ``pallas`` / ``pallas-interpret``) run only the fastest-k workers'
nonzero tiles and decode against a cached per-pattern inverse; traced
callers (jit/grad/shard_map) and the ``reference`` backend keep the
original dense einsum + solve numerics.  ``backend=None``/"auto" picks
the backend from the weight's measured block density.

Storage/computation overhead vs an uncoded TP layer is omega/k_A (the
paper's whole point: omega ~= s+1 << k_A), while tolerating any s
straggling devices per matmul.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..core.assignment import MVScheme
from ..core.stability import find_good_coefficients
from ..runtime import CodedExecutor


@dataclass
class CodedLinear:
    scheme: MVScheme
    coded: jnp.ndarray       # (n, d_in, c) coded block-columns of W
    G: jnp.ndarray           # (n, k) decode system matrix
    d_out: int
    backend: str | None = None
    _executor: CodedExecutor | None = field(
        default=None, repr=False, compare=False)
    _plan: object | None = field(default=None, repr=False, compare=False)

    @staticmethod
    def build(w: jnp.ndarray, n_workers: int, stragglers: int,
              seed: int | None = None, stability_trials: int = 0,
              backend: str | None = None,
              scheme: str = "proposed") -> "CodedLinear":
        """Encode a (d_in, d_out) weight for n workers / s stragglers.

        Routes through ``repro.api.compile_plan``: ``scheme`` is any
        registered mv scheme name and ``backend=None``/"auto" picks
        packed/reference from the weight's measured block density.
        """
        from ..api.plan import compile_plan  # noqa: PLC0415 - layering
        from ..api.schemes import make_scheme  # noqa: PLC0415

        k = n_workers - stragglers
        sch = make_scheme(scheme, n=n_workers, k_A=k)
        if seed is None:
            if stability_trials > 0:
                seed = find_good_coefficients(
                    sch, trials=stability_trials, max_patterns=64).best_seed
            else:
                seed = 0
        plan = compile_plan(w, scheme=sch, seed=seed, backend=backend)
        # compile_plan keeps the shards in w.dtype (_match_dtype)
        layer = CodedLinear(scheme=sch, coded=plan.executor.coded,
                            G=plan.executor.G, d_out=w.shape[1],
                            backend=plan.backend)
        if not isinstance(layer.coded, jax.core.Tracer):
            layer._executor, layer._plan = plan.executor, plan
        return layer

    # ------------------------------------------------------------------

    def plan(self):
        """The compiled ``CodedPlan`` backing this layer."""
        from ..api.plan import CodedPlan  # noqa: PLC0415 - layering

        if isinstance(self.coded, jax.core.Tracer):
            # built inside a trace: throwaway plan, never cached; G may
            # itself be traced here -- the reference executor never
            # consults the plan-level G, so pass it through untouched
            return CodedPlan(scheme=self.scheme, kind="mv",
                             backend="reference", seed=0,
                             G=self.G, r=self.d_out,
                             executor=self.executor())
        if self._plan is None:
            self._plan = CodedPlan(
                scheme=self.scheme, kind="mv",
                backend=self.executor().backend, seed=0,
                G=np.asarray(self.G), r=self.d_out,
                executor=self.executor())
        return self._plan

    def executor(self) -> CodedExecutor:
        if isinstance(self.coded, jax.core.Tracer):
            # layer built inside a trace: use a throwaway reference
            # executor; caching it would leak the tracer across traces
            return CodedExecutor(self.coded, self.G, self.scheme.k_A,
                                 self.d_out, backend="reference")
        if self._executor is None:
            self._executor = CodedExecutor(
                self.coded, self.G, self.scheme.k_A, self.d_out,
                backend=self.backend)
        return self._executor

    def worker_compute(self, x: jnp.ndarray) -> jnp.ndarray:
        """All-worker products: x (..., d_in) -> (n, ..., c).

        The all-n contract exists for the shard_map path and the tests;
        the fused fastest-k fast path lives in ``apply``.
        """
        return jnp.einsum("ntc,...t->n...c", self.coded, x)

    def decode(self, y: jnp.ndarray, done: jnp.ndarray | None) -> jnp.ndarray:
        """y (n_tasks, ..., c) worker results -> (..., d_out).

        ``done`` is worker-level; Delta-partition schemes (scs36 /
        class29 run ``tasks_per_worker`` tasks each) expand it to task
        rows via the plan.
        """
        return self.executor().decode(y, self.plan()._task_done(done))

    def apply(self, x: jnp.ndarray, done: jnp.ndarray | None = None
              ) -> jnp.ndarray:
        """Single-device (vmap-style virtual workers) coded apply."""
        ex = self.executor()
        if ex.backend == "reference" or isinstance(x, jax.core.Tracer):
            return self.decode(self.worker_compute(x), done)
        lead = x.shape[:-1]
        out = self.plan().matvec(x.reshape(-1, x.shape[-1]), done)
        return out.reshape(lead + (self.d_out,)).astype(x.dtype)

    # ------------------------------------------------------------------

    def apply_sharded(self, mesh, axis: str, x: jnp.ndarray,
                      done: jnp.ndarray | None = None) -> jnp.ndarray:
        """shard_map apply: each 'model'-axis slice computes its shard's
        product; results all-gather over the axis; decode is replicated
        (k x k solve on a tiny matrix)."""
        from jax.sharding import PartitionSpec as P  # noqa: PLC0415

        n = self.scheme.n
        if mesh.shape[axis] != n:
            raise ValueError(f"mesh axis {axis} has {mesh.shape[axis]} "
                             f"devices, scheme expects n={n}")
        if done is None:
            done = jnp.ones(n, bool)

        def worker(coded_shard, xx, dd):
            # coded_shard: (1, d_in, c) local slice
            y_local = jnp.einsum("tc,...t->...c", coded_shard[0], xx,
                                 precision=jax.lax.Precision.HIGHEST)
            y_all = jax.lax.all_gather(y_local, axis)      # (n, ..., c)
            return self.decode(y_all, dd)

        from .ctx import auto_mesh  # noqa: PLC0415

        fn = jax.shard_map(
            worker, mesh=auto_mesh(mesh),
            in_specs=(P(axis), P(), P()),
            out_specs=P(),
            # the decode of the all-gathered results is identical on
            # every device; replication can't be statically inferred
            check_vma=False,
        )
        return fn(self.coded, x, done)
