"""Backend probe shared by the kernel dispatch sites."""

from __future__ import annotations

import jax


def is_tpu_backend() -> bool:
    """True when the default backend is a TPU."""
    return jax.default_backend() == "tpu"
