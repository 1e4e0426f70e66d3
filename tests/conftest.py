"""Suite-wide setup: makes ``src`` importable when pytest runs from the
repo root without PYTHONPATH (the tier-1 command sets it; direct IDE
runs often don't)."""

from __future__ import annotations

import sys
from pathlib import Path

_SRC = str(Path(__file__).resolve().parent.parent / "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)
