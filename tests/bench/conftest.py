"""Makes the benchmark package (``bench``) importable from the checkout
root, beside ``src`` which the suite's own conftest adds."""

from __future__ import annotations

import sys
from pathlib import Path

_ROOT = str(Path(__file__).resolve().parents[2])
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)
