"""Overlay patch kernel — the Overlay-VMA mechanism as a TPU kernel.

Materializes a restored tensor from (a) a device-resident shared BASE image,
(b) a sparse stream of PRIVATE pages fetched from the snapshot, and (c)
implicit ZERO pages, according to a per-page classification table — in one
pass, on device.

TPU adaptation: the kernel-side analogue of installing PTEs from the
pre-balanced B-tree.  The page->source table rides in scalar-prefetch SMEM
so each grid step's BlockSpec ``index_map`` *chooses which private page to
stream into VMEM* (pages classified BASE/ZERO fetch an arbitrary clamped
private block but never read it — select masks it out).  One grid step =
one page; page size is the VMEM tile.

Pages are laid out ``(n_pages, page_elems // LANES, LANES)``: each block's
last two dimensions are then the whole page, which the TPU compiler accepts
for any page count (a ``(1, page_elems)`` row block is refused as soon as a
tensor spans more than one page).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KIND_ZERO, KIND_BASE, KIND_PRIVATE = 0, 1, 2
LANES = 128  # TPU vector lane width: the last dimension of every page


def tiles(page_bytes: int, dtype) -> bool:
    """Whether a page of ``page_bytes`` views as whole ``(rows, LANES)``
    tiles of ``dtype`` (the kernel takes no other page)."""
    return page_bytes % (jnp.dtype(dtype).itemsize * LANES) == 0


def page_shape(page_elems: int) -> tuple:
    """The (rows, LANES) view of one page; ``page_elems`` must be a
    multiple of :data:`LANES`."""
    if page_elems % LANES:
        raise ValueError(f"page of {page_elems} elements does not tile {LANES} lanes")
    return (page_elems // LANES, LANES)


def _kernel(kinds_ref, src_ref, base_ref, priv_ref, out_ref):
    i = pl.program_id(0)
    kind = kinds_ref[i]
    base_page = base_ref[...]
    priv_page = priv_ref[...]
    zero = jnp.zeros_like(base_page)
    out_ref[...] = jnp.where(
        kind == KIND_PRIVATE, priv_page, jnp.where(kind == KIND_BASE, base_page, zero)
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def overlay_patch_kernel(
    base: jax.Array,  # (n_pages, rows, LANES) device-resident shared image
    priv: jax.Array,  # (n_priv, rows, LANES) private pages from the snapshot
    kinds: jax.Array,  # (n_pages,) int32 {ZERO, BASE, PRIVATE}
    src: jax.Array,  # (n_pages,) int32 private-page index (PRIVATE only)
    interpret: bool = False,
) -> jax.Array:
    n_pages, rows, lanes = base.shape
    n_priv = max(priv.shape[0], 1)
    priv = priv if priv.shape[0] else jnp.zeros((1, rows, lanes), priv.dtype)
    block = (None, rows, lanes)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # kinds, src ride in SMEM ahead of the grid
        grid=(n_pages,),
        in_specs=[
            pl.BlockSpec(block, lambda i, kinds, src: (i, 0, 0)),
            # data-dependent streaming: which private page lands in VMEM
            pl.BlockSpec(
                block,
                lambda i, kinds, src: (jnp.clip(src[i], 0, n_priv - 1), 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec(block, lambda i, kinds, src: (i, 0, 0)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(base.shape, base.dtype),
        interpret=interpret,
        name="overlay_patch",
    )(kinds.astype(jnp.int32), src.astype(jnp.int32), base, priv)
