"""AOT-compilation (``jit(...).lower().compile()``) result helpers."""
from __future__ import annotations

from typing import Any, Dict


def cost_analysis(compiled) -> Dict[str, Any]:
    """Flat metrics dict from a compiled executable, or {} if unavailable."""
    return dict(compiled.cost_analysis() or {})
