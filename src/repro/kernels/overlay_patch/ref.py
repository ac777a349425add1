"""Pure-jnp oracle for the overlay patch kernel (pages of any shape)."""
from __future__ import annotations

import jax.numpy as jnp

KIND_ZERO, KIND_BASE, KIND_PRIVATE = 0, 1, 2


def overlay_patch_ref(base, priv, kinds, src):
    page = base.shape[1:]
    priv = priv if priv.shape[0] else jnp.zeros((1, *page), priv.dtype)
    gathered = priv[jnp.clip(src, 0, priv.shape[0] - 1)]
    kinds = kinds.reshape((-1,) + (1,) * len(page))
    return jnp.where(
        kinds == KIND_PRIVATE,
        gathered,
        jnp.where(kinds == KIND_BASE, base, jnp.zeros_like(base)),
    )
