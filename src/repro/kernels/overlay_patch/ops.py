"""Public wrapper: restore a flat tensor from base/private/zero pages.

Also provides ``plan_from_itable`` to turn a JIF IntervalTable into the
dense (kinds, src) page tables the kernel consumes (built once at restore,
host-side — the "pre-balanced B-tree slotted directly in", §4.2).

Two plan flavors exist because the two restore paths stage private pages
differently:

* :func:`plan_from_itable` keeps ``src`` as ABSOLUTE data-segment chunk
  offsets — what a caller holding the whole data segment indexes with.
* :func:`compact_plan_from_itable` renumbers private pages 0..n_priv-1 in
  page order — what the device fast path uploads: the restorer reads ONLY
  the private chunks into a compact staging buffer (no intermediate full
  host tensor) and the kernel gathers from that dense array.

:func:`overlay_patch_device` is the serving-path entry: the Pallas kernel
on TPU, a jitted version of the pure-jnp oracle on CPU (interpret-mode
Pallas executes one Python step per page — far too slow for restores).
"""
from __future__ import annotations

from functools import lru_cache
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.overlay import KIND_PRIVATE, IntervalTable
from repro.kernels.overlay_patch.kernel import overlay_patch_kernel, page_shape
from repro.kernels.overlay_patch.ref import overlay_patch_ref


def plan_from_itable(table: IntervalTable) -> Tuple[np.ndarray, np.ndarray]:
    n = table.n_pages
    kinds = np.zeros((n,), np.int32)
    src = np.zeros((n,), np.int32)
    for start, count, kind, s in table.table:
        kinds[start : start + count] = kind
        if kind == KIND_PRIVATE:
            src[start : start + count] = np.arange(s, s + count)
    return kinds, src


def compact_plan_from_itable(
    table: IntervalTable,
) -> Tuple[np.ndarray, np.ndarray, List[Tuple[int, int, int]], int]:
    """(kinds, src, runs, n_priv) with ``src`` indexing a COMPACT private
    array: private pages are numbered 0..n_priv-1 in page order.  ``runs``
    is the read plan — (compact_slot, data_chunk, count) per private run —
    mapping the JIF data segment onto the compact staging buffer."""
    n = table.n_pages
    kinds = np.zeros((n,), np.int32)
    src = np.zeros((n,), np.int32)
    runs: List[Tuple[int, int, int]] = []
    k = 0
    for start, count, kind, s in table.table:
        kinds[start : start + count] = kind
        if kind == KIND_PRIVATE:
            src[start : start + count] = np.arange(k, k + count)
            runs.append((k, int(s), int(count)))
            k += count
    return kinds, src, runs, k


def overlay_patch(
    base: jax.Array,
    priv: jax.Array,
    kinds: jax.Array,
    src: jax.Array,
    interpret: bool = False,
) -> jax.Array:
    """Patched ``(n_pages, *page)`` output on device.  Flat
    ``(n_pages, page_elems)`` pages are viewed as the kernel's
    ``(rows, LANES)`` tiles and handed back flat."""
    tile = page_shape(int(np.prod(base.shape[1:])))
    out = overlay_patch_kernel(
        base.reshape(base.shape[0], *tile), priv.reshape(priv.shape[0], *tile),
        kinds, src, interpret=interpret,
    )
    return out.reshape(base.shape)


@lru_cache(maxsize=1)
def _ref_jit():
    return jax.jit(overlay_patch_ref)


def overlay_patch_device(
    base: jax.Array,
    priv: jax.Array,
    kinds: jax.Array,
    src: jax.Array,
) -> jax.Array:
    """Serving-path overlay patch over ``(n_pages, rows, LANES)`` pages: one
    fused on-device pass, dispatched by backend.  TPU runs the Pallas
    kernel (scalar-prefetch page table in SMEM) and raises if it cannot;
    every other backend runs the jitted oracle — same math, same output,
    compiled gather instead of per-page interpret steps."""
    if jax.default_backend() == "tpu":
        return overlay_patch_kernel(base, priv, kinds, src)
    return _ref_jit()(base, priv, kinds, src)
