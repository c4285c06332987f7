"""Shared kernel plumbing: interpret-mode detection and tiling helpers."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["use_interpret", "pick_block", "SUBLANES", "ZERO", "pad_rows"]

# A TPU block's second-to-last dimension must be a multiple of the 8
# sublanes of a 32-bit vreg (or span the whole array).
SUBLANES = 8

# Block index for an unsplit axis. Index maps must return int32, and with
# x64 enabled a bare Python 0 traces as int64, which Mosaic cannot lower.
ZERO = np.int32(0)


def use_interpret() -> bool:
    """Pallas kernels execute in interpret mode off-TPU (this container is
    CPU-only; TPU v5e is the compile target, not the runtime)."""
    return jax.default_backend() != "tpu"


def pick_block(n: int, preferred: int) -> int:
    """Largest divisor of n that is ≤ preferred (block shapes must tile)."""
    b = min(n, preferred)
    while n % b:
        b -= 1
    return b


def pad_rows(x: jnp.ndarray, multiple: int = SUBLANES) -> jnp.ndarray:
    """Pad axis 0 up to a multiple by repeating the last row.

    Prime-indexed operands (residues and their per-prime tables) are
    padded together, so a padded row is a copy of a valid prime's work;
    callers slice its results away.
    """
    pad = -x.shape[0] % multiple
    if not pad:
        return x
    return jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1), mode="edge")
