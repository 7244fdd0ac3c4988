"""Installed-JAX version, for logs and error messages.

The repo runs on the JAX the environment installs (0.9.0, with libtpu
0.0.34 on the chip); the portability layer spells each symbol the way that
release does and supports no other.
"""
from __future__ import annotations

import jax


def _parse(version: str) -> tuple:
    parts = []
    for piece in version.split(".")[:3]:
        digits = "".join(ch for ch in piece if ch.isdigit())
        if not digits:
            break
        parts.append(int(digits))
    return tuple(parts)


JAX_VERSION = _parse(jax.__version__)


def version_summary() -> str:
    """One-line provenance string for logs and error messages."""
    return f"jax {jax.__version__}"
