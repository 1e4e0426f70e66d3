"""The plain reference and the comparison that decides ``correct``.

The reference is the product the configuration states, ``A^T x`` or
``A^T B``, in float64 on the host from the operands the benchmark made.
It uses nothing of the program: no coded shards, no decode.
"""

from __future__ import annotations

import numpy as np


def host64(a) -> np.ndarray:
    """A device or host array as a float64 host array."""
    return np.asarray(a, dtype=np.float64)


def sparse64(a):
    """The host float64 CSR form of an operand held on the device."""
    from scipy import sparse  # noqa: PLC0415

    return sparse.csr_matrix(np.asarray(a), dtype=np.float64)


def mv_expected(a_csr, x) -> np.ndarray:
    """A^T x for x (batch, t): (batch, r) in float64."""
    return np.asarray((a_csr.T @ host64(x).T).T)


def mm_expected(a_csr, b) -> np.ndarray:
    """A^T B in float64, for B (t, columns)."""
    return np.asarray(a_csr.T @ host64(b))


def lower_precision_dot(a, b, precision: str):
    """``a @ b`` on the device as a control computes it: ``high`` at
    ``Precision.HIGH`` (three bfloat16 passes on the TPU; the CPU
    ignores it), ``bf16`` with both operands rounded to bfloat16 (one
    pass); float32 accumulation either way."""
    import jax  # noqa: PLC0415
    import jax.numpy as jnp  # noqa: PLC0415

    if precision == "high":
        return jnp.dot(a, b, precision=jax.lax.Precision.HIGH)
    if precision == "bf16":
        return jnp.dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    raise ValueError(f"unknown control precision {precision!r}")


def rel_err(y, ref: np.ndarray) -> float:
    """``max|y - ref| / max|ref|``: the largest error against the
    output's scale.  NaN or inf in ``y`` reads as inf."""
    y = np.asarray(y, dtype=np.float64)
    if y.shape != ref.shape:
        return float("inf")
    err = float(np.abs(y - ref).max() / np.abs(ref).max())
    return err if np.isfinite(err) else float("inf")


def off_path_calls(plan, backend: str) -> int:
    """Executor calls that left the compiled kernel path ``backend``
    (a backend override, the CPU default, a traced-input fallback to
    the reference path, interpret mode): every one counts, and a plan
    that resolved to another backend counts as one more."""
    ex = plan.executor
    off = sum(c for path, c in ex.calls.items() if path != backend)
    if plan.backend != backend or ex.backend != backend:
        off += 1
    return int(off)
