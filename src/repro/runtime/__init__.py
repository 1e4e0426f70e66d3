"""Runtime: the sparsity-aware coded execution engine.

This package turns the paper's cost model into the repo's actual hot
path.  A weight-omega encoding guarantees each coded shard mixes only
``omega`` of the ``k_A`` source block-columns, so a worker's nonzero
tiles -- and hence its MXU/FLOP cost -- scale with ``omega / k_A`` of
the dense cost (omega ~= s+1 << k_A).  The executor realises that
scaling end-to-end:

  * ``pack``         -- coded shards -> packed block-sparse (a_data, a_idx)
    operands; only nonzero tiles are stored or multiplied.
  * ``decode_cache`` -- per-straggler-pattern decode plans (cached k x k
    inverse), so repeated applies under the same ``done`` mask never
    re-run a solve.
  * ``executor``     -- ``CodedExecutor`` with ``reference`` / ``packed`` /
    ``pallas`` / ``pallas-interpret`` backends; every coded call site
    (``CodedOperator``, ``CodedLinear``, ``coded_matvec``/``matmat``,
    the serving engine) routes through it.  Its kernel path runs one
    product over device groups: an unplaced operator is one group on
    the default device; ``devices=`` (``compile_plan(..., devices=)``)
    places an MV operator's workers over several, one launch a device,
    decoded from the first devices to finish.
  * ``placement``    -- the executor's device-free placement policy:
    workers a device, which devices to launch on, the harvest by
    completion, tested with scripted readiness.

Force a backend with the ``REPRO_CODED_BACKEND`` environment variable
(e.g. ``REPRO_CODED_BACKEND=packed`` on CPU, ``pallas-interpret`` to
validate the kernels without a TPU) or pass ``backend=`` explicitly;
the platform default is ``pallas`` on TPU and ``reference`` elsewhere.
"""

from .decode_cache import DecodeCache, DecodePlan  # noqa: F401
from .executor import (  # noqa: F401
    BACKENDS,
    ENV_BACKEND,
    CodedExecutor,
    encode_blocks,
    is_concrete,
    resolve_backend,
    support_tables,
)
from .pack import PackedShards, pack_coded_blocks, unpack_coded_blocks  # noqa: F401
