"""Device-resident restore fast path: host→HBM upload stream + base cache.

The host restore pipeline stops at host memory; the eager install path then
pays a synchronous per-tensor device copy on the prefetcher thread, so the
read stream stalls behind every upload (serialization-bound, not
read-bandwidth-bound).  This module closes that gap:

* :class:`UploadStream` — a bounded host→HBM upload ring.  The
  prefetcher's finalize enqueues an upload job and returns to reading; an
  issuer thread dispatches each job's device transfer without waiting for
  it, and a lander thread waits for the jobs to land, in order, and
  resolves their handles.  At most ``depth`` jobs are outstanding
  (default :data:`UPLOAD_DEPTH`), queued and in flight together, so
  several transfers cross at once and the reader only blocks when all
  ``depth`` are outstanding — uploads overlap with each other, with
  ongoing disk reads, and (because completion is tracked per tensor) with
  layer-gated decode in the function instance.  The pool's pre-zeroed
  staging buffers are the pinned-slot analogue: the lander hands each one
  back to the pool only after the transfer that reads it landed,
  re-zeroing it there, off the reader's and the issuer's paths.

* :class:`DeviceImageCache` — base images resident in HBM once per node.
  Each (image, tensor) entry holds the base's tensor on device, either in
  its own shape or as the overlay kernel's pages, charged to the node
  ledger under the ``device_image`` kind and evictable via its own
  reclaim-ladder rung (order 1: after residual tails, before host base
  images — a dropped device base costs one re-upload from host, never a
  disk read).  A delta restore shares a tensor it left unchanged (every
  page BASE) as the cache's array: no ring job, no copy.  A tensor with
  private pages uploads ONLY those and materializes on device with the
  overlay-patch kernel: BASE pages come from the shared HBM-resident base
  pages, ZERO pages are free, and no intermediate full host tensor is ever
  built.

* :class:`DevicePath` — the bundle a :class:`~repro.core.restore
  .SpiceRestorer` takes as its ``device_path=`` mode.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import queue
import threading
import time
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from repro.core.cache import BaseImage
from repro.core.memory import (
    KIND_DEVICE_IMAGE,
    MemoryPressureError,
    NodeMemoryManager,
)
from repro.core.spans import NO_REQ, span


# Jobs the upload ring keeps outstanding: the fewest transfers in flight
# that reached the host link's rate on a TPU v5e (PERF.md, section 5).
UPLOAD_DEPTH = 8


def _default_install(arr: np.ndarray):
    """Host array -> device array.  MUST copy: on CPU ``jnp.asarray`` can
    alias the staging buffer, which the pool recycles and re-zeroes (on TPU
    ``device_put`` always copies into HBM)."""
    import jax.numpy as jnp

    return jnp.array(arr, copy=True)


@functools.lru_cache(maxsize=1)
def _unpage():
    """Jitted: the restored tensor from its patched ``(n_pages, ...)``
    pages, flattened, trimmed to the tensor's length and reshaped — one
    dispatch per fused job."""
    import jax

    def unpage(pages, shape):
        return pages.reshape(-1)[: math.prod(shape)].reshape(shape)

    return jax.jit(unpage, static_argnums=1)


@dataclasses.dataclass
class FusedPlan:
    """Per-tensor device-patch plan, built host-side at restore planning
    time (the itable is already resident — zero deserialization).  ``src``
    indexes the COMPACT private staging buffer (pages 0..n_priv-1 in page
    order); ``runs`` maps JIF data-segment chunks onto compact slots."""

    name: str
    shape: Tuple[int, ...]
    dtype: str
    nbytes: int
    page_bytes: int
    page_elems: int
    n_pages: int
    n_priv: int
    kinds: np.ndarray
    src: np.ndarray
    runs: List[Tuple[int, int, int]]  # (compact_slot, data_chunk, count)
    base_pages: Optional[object] = None  # device (n_pages, rows, LANES) or None

    @property
    def priv_bytes(self) -> int:
        return self.n_priv * self.page_bytes


@dataclasses.dataclass(eq=False)
class _Job:
    """One upload on the ring: ``issue`` dispatches its transfers (and, for
    a fused job, the patch) without waiting, leaving the handle's array in
    ``arr`` and every transfer that reads ``buf`` in ``reads``."""

    issue: Callable[["_Job"], None]
    handle: object
    buf: Optional[np.ndarray]
    release: Optional[Callable]
    uploaded: int  # bytes that cross to HBM
    patched: int  # tensor bytes the patch materializes (fused jobs)
    fused: bool
    stats: object
    span_args: Dict
    t_issue: float = 0.0
    arr: object = None
    reads: Tuple = ()
    error: Optional[BaseException] = None


class UploadStream:
    """Bounded host→HBM upload ring shared by every restore on a node.

    Two daemon threads share the work.  The issuer takes submitted jobs in
    order and dispatches each one's transfer (and patch) without waiting for
    it; the lander takes issued jobs in the same order, waits for each to
    land, hands its staging buffer back, and only then resolves its
    :class:`TensorHandle` — so handles resolve in submit order, to arrays on
    the device, and a staging buffer is never released under a transfer
    that reads it.  At most ``depth`` jobs are outstanding (submitted and
    not yet landed, queued and in flight together); ``submit`` blocks the
    producer (the prefetch reader thread) only while all of them are, the
    trade-off that bounds the transfers in flight.  Each job resolves
    exactly one handle (``set`` on success, ``fail`` on error: at once for
    an error while issuing, after the wait for one while landing), so a
    failed upload never hangs a waiter."""

    def __init__(self, depth: int = UPLOAD_DEPTH, name: str = "upload-stream",
                 install: Optional[Callable] = None):
        self.name = name
        self.depth = max(1, int(depth))
        self.install = install or _default_install
        self._issue_q: "queue.Queue" = queue.Queue()
        self._land_q: "queue.Queue" = queue.Queue()
        self._cv = threading.Condition(threading.Lock())
        self._pending = 0  # submitted jobs that have not landed
        self._in_flight = 0  # issued jobs that have not landed
        self._last_land = 0.0  # lander only
        self._threads: Tuple[threading.Thread, ...] = ()
        self._closed = False
        self.stats = {
            "uploads": 0,
            "fused_patches": 0,
            "uploaded_bytes": 0,
            "patched_bytes": 0,
            "upload_s": 0.0,
            "failures": 0,
            "issued_while_busy": 0,  # issued while an earlier job was in flight
            "in_flight_max": 0,
        }

    # ------------------------------------------------------------ internals
    def _span_args(self, span_args: Optional[Dict]) -> Dict:
        return span_args or {"function": self.name, "req": NO_REQ}

    def _submit(self, job: _Job) -> None:
        with span("spice.ring_wait", **job.span_args):
            with self._cv:
                # backpressure: wait while ``depth`` jobs are outstanding
                self._cv.wait_for(
                    lambda: self._closed or self._pending < self.depth
                )
                if self._closed:
                    raise RuntimeError(f"upload stream {self.name!r} is closed")
                self._pending += 1
                if not self._threads:
                    self._threads = tuple(
                        threading.Thread(target=loop, name=f"{self.name}-{role}",
                                         daemon=True)
                        for loop, role in ((self._issue_loop, "issuer"),
                                           (self._land_loop, "lander"))
                    )
                    for th in self._threads:
                        th.start()
        self._issue_q.put(job)

    def _fail(self, job: _Job, exc: BaseException) -> None:
        job.error = exc
        with self._cv:
            self.stats["failures"] += 1
        job.handle.fail(exc)

    def _issue_loop(self) -> None:
        while True:
            job = self._issue_q.get()
            if job is None:
                self._land_q.put(None)
                return
            self._issue(job)
            job = None  # hold no tensor while idle

    def _issue(self, job: _Job) -> None:
        job.t_issue = time.perf_counter()
        with span("spice.upload.issue", **job.span_args):
            try:
                job.issue(job)
            except BaseException as exc:  # noqa: BLE001 — typed via handle
                self._fail(job, exc)
        with self._cv:
            if job.error is None:
                self.stats["issued_while_busy"] += int(self._in_flight > 0)
                self.stats["in_flight_max"] = max(
                    self.stats["in_flight_max"], self._in_flight + 1
                )
            self._in_flight += 1
        self._land_q.put(job)

    def _land_loop(self) -> None:
        while True:
            job = self._land_q.get()
            if job is None:
                return
            self._land(job)
            job = None  # hold no landed tensor while idle

    def _land(self, job: _Job) -> None:
        import jax

        try:
            with span("spice.upload.land", **job.span_args):
                if job.error is None:
                    try:
                        jax.block_until_ready(job.arr)
                    except BaseException as exc:  # noqa: BLE001
                        self._fail(job, exc)
                else:
                    # failed while issuing: let whatever reached the
                    # device land before its source is recycled
                    for x in job.reads:
                        try:
                            jax.block_until_ready(x)
                        except Exception:  # noqa: BLE001 — reported at issue
                            pass
            land = time.perf_counter()
            if job.release is not None and job.buf is not None:
                job.release(job.buf)
            if job.error is None:
                # ring time this landing added: the restore's sum is the
                # union of its jobs' in-flight intervals
                dt = max(0.0, land - max(job.t_issue, self._last_land))
                self._last_land = land
                job.handle.set(job.arr)
                self._note(job, dt)
        finally:
            with self._cv:
                self._in_flight -= 1
                self._pending -= 1
                self._cv.notify_all()

    def _note(self, job: _Job, dt: float) -> None:
        with self._cv:
            self.stats["uploads"] += 1
            self.stats["upload_s"] += dt
            self.stats["uploaded_bytes"] += job.uploaded
            if job.fused:
                self.stats["fused_patches"] += 1
                self.stats["patched_bytes"] += job.patched
        if job.stats is not None:
            job.stats.add(upload_s=dt, uploaded_bytes=job.uploaded,
                          patched_on_device_bytes=job.patched)

    # ----------------------------------------------------------------- API
    def upload_full(self, handle, buf: np.ndarray, *, shape, dtype: str,
                    nbytes: int, stats=None, release=None,
                    span_args: Optional[Dict] = None) -> None:
        """Enqueue a whole-tensor upload: the staging buffer holds the full
        host tensor (base memcpy + private reads + zero pages); the device
        copy is issued on the issuer thread, overlapped with further reads
        and earlier transfers.  ``span_args`` (``function`` and ``req``)
        label the upload's spans."""
        span_args = self._span_args(span_args)

        def issue(job: _Job) -> None:
            view = buf[:nbytes].view(np.dtype(dtype))
            view = view.reshape(shape) if shape else view.reshape(())
            with span("spice.upload.put", **span_args):
                job.arr = self.install(view)
            job.reads = (job.arr,)

        self._submit(_Job(issue, handle, buf, release, nbytes, 0, False,
                          stats, span_args))

    def upload_fused(self, handle, plan: FusedPlan,
                     buf: Optional[np.ndarray], *, stats=None,
                     release=None, span_args: Optional[Dict] = None) -> None:
        """Enqueue a fused upload+patch: only the compact private pages in
        ``buf`` cross to the device; the full tensor materializes there via
        the overlay-patch kernel against the HBM-resident base pages
        (``plan.base_pages``; ZERO pages cost nothing)."""
        span_args = self._span_args(span_args)

        def issue(job: _Job) -> None:
            import jax.numpy as jnp

            from repro.kernels.overlay_patch.kernel import page_shape
            from repro.kernels.overlay_patch.ops import overlay_patch_device

            dtype = np.dtype(plan.dtype)
            page = page_shape(plan.page_elems)
            if plan.n_priv and buf is not None:
                priv_host = (
                    buf[: plan.priv_bytes]
                    .view(dtype)
                    .reshape(plan.n_priv, *page)
                )
                with span("spice.upload.put", **span_args):
                    priv = self.install(priv_host)
                job.reads = (priv,)
            else:
                priv = jnp.zeros((1, *page), dtype)
            base = plan.base_pages
            if base is None:  # ZERO/PRIVATE-only tensor: free base
                base = jnp.zeros((plan.n_pages, *page), dtype)
            # the page plan goes as host int32 arrays: the jitted call
            # moves them with its arguments, in one dispatch
            out = overlay_patch_device(base, priv, plan.kinds, plan.src)
            job.arr = _unpage()(out, plan.shape)

        self._submit(_Job(issue, handle, buf, release, plan.priv_bytes,
                          plan.nbytes, True, stats, span_args))

    def flush(self, timeout: Optional[float] = None) -> bool:
        """Block until every enqueued upload landed (tests/benchmarks)."""
        with self._cv:
            return self._cv.wait_for(lambda: self._pending == 0, timeout)

    def close(self, timeout: float = 5.0) -> None:
        """Drain outstanding uploads, issued and landed, and stop both
        threads (idempotent)."""
        with self._cv:
            if self._closed:
                return
            self._closed = True
            self._cv.notify_all()  # a producer blocked on a full ring
            threads = self._threads
        self.flush(timeout)
        if threads:
            self._issue_q.put(None)  # the issuer passes it on to the lander
            for th in threads:
                th.join(timeout)

    def snapshot_stats(self) -> Dict[str, float]:
        with self._cv:
            return dict(self.stats)


class DeviceImageCache:
    """HBM-resident base tensors, shared by every fused restore on a node.

    Two kinds of entry: per (base image, tensor, dtype, shape) the base's
    tensor as is (:meth:`get_tensor`, for tensors a delta left unchanged),
    and per (base image, tensor, dtype, page geometry) its raw bytes padded
    to the restored tensor's page count (:meth:`get_pages`, the overlay
    kernel's base).  Each is installed on device ONCE — the ROADMAP
    scenario where thousands of fine-tunes of one base share a single
    HBM-resident copy.
    Attached to the node ledger, entries are charged as ``device_image``
    regions and LRU-evicted by the pressure reclaimer (rung
    ``RECLAIM_ORDER``); every entry is recoverable from the host
    :class:`BaseImage`, so the rung may drain the cache entirely."""

    RECLAIM_ORDER = 1  # residual (0) -> device images -> chunk CAS (2) ->
    # host image cache (3)

    def __init__(self, capacity_bytes: int = 4 << 30,
                 install: Optional[Callable] = None):
        self.capacity = capacity_bytes
        self.install = install or _default_install
        self._entries: "OrderedDict[Tuple, Tuple[object, int]]" = OrderedDict()
        self._regions: Dict[Tuple, object] = {}
        self._lock = threading.Lock()
        self._memory: Optional[NodeMemoryManager] = None
        self.total_bytes = 0
        self.stats = {
            "hits": 0, "tensor_hits": 0, "misses": 0, "evictions": 0,
            "built_bytes": 0, "base_bytes_served": 0,
        }

    # --------------------------------------------------------------- ledger
    def attach(self, memory: NodeMemoryManager) -> None:
        """Charge resident entries to the node ledger and register the LRU
        eviction as the ladder's device-image rung."""
        evicted = []
        with self._lock:
            if self._memory is memory:
                return
            self._memory = memory
            entries = list(self._entries.items())
        for key, (_dev, nbytes) in entries:
            try:
                region = memory.reserve(
                    nbytes, KIND_DEVICE_IMAGE,
                    owner="/".join(map(str, key[:2])), block=False,
                )
            except MemoryPressureError:
                # always recoverable from the host base: drop, don't raise
                self._drop(key)
                continue
            region.commit()
            with self._lock:
                if key in self._entries:
                    self._regions[key] = region
                else:
                    evicted.append(region)
        for r in evicted:
            r.release()
        memory.register_reclaimer("device-image", self.reclaim, self.RECLAIM_ORDER)

    # ----------------------------------------------------------------- API
    def get_pages(self, base: BaseImage, tensor_name: str, n_pages: int,
                  page_elems: int, dtype) -> Optional[object]:
        """Device ``(n_pages, rows, LANES)`` base pages for one tensor (the
        overlay kernel's tiling), building and charging the entry on first
        use.  Returns None when the entry cannot be served (page-size
        mismatch, tensor absent from the base, or the ledger cannot admit
        the bytes even after reclaim) — the caller falls back to the host
        path for that tensor."""
        from repro.kernels.overlay_patch.kernel import page_shape

        dtype = np.dtype(dtype)
        page_bytes = page_elems * dtype.itemsize
        key = (base.name, tensor_name, dtype.str, int(n_pages), int(page_elems))
        hit = self._lookup(key, "hits")
        if hit is not None:
            return hit
        if base.page_size != page_bytes or base.digests(tensor_name) is None:
            return None
        # pad the base's raw bytes to the restored tensor's page count (a
        # shorter base cannot own pages past its length — classify never
        # marks them BASE — so zero padding is safe)
        raw = base.chunk_bytes(tensor_name, 0, n_pages)
        host = np.zeros(n_pages * page_bytes, np.uint8)
        host[: len(raw)] = raw[: n_pages * page_bytes]
        host = host.view(dtype).reshape(n_pages, *page_shape(page_elems))
        return self._admit(key, host, "hits")

    def get_tensor(self, base: BaseImage, tensor_name: str, shape,
                   dtype) -> Optional[object]:
        """The base's tensor on device in its own ``shape`` and ``dtype``,
        building and charging the entry on first use.  A restore serves a
        tensor whose pages are all BASE as this one array, shared with
        every other restore of the node: JAX arrays are immutable, so the
        sharing is copy-on-write.  Returns None when the entry cannot be
        served (the base holds the tensor at another shape or dtype, hence
        byte length, or not at all, or the ledger cannot admit the bytes
        even after reclaim) — the caller takes the ring path instead."""
        dtype = np.dtype(dtype)
        shape = tuple(int(d) for d in shape)
        key = (base.name, tensor_name, dtype.name, shape)
        hit = self._lookup(key, "tensor_hits")
        if hit is not None:
            return hit
        if base.spec(tensor_name) != (shape, dtype):
            return None
        host = base.tensor_bytes(tensor_name).view(dtype).reshape(shape)
        return self._admit(key, host, "tensor_hits")

    def _lookup(self, key, hit_stat: str) -> Optional[object]:
        with self._lock:
            hit = self._entries.get(key)
            if hit is None:
                return None
            self.stats[hit_stat] += 1
            self._entries.move_to_end(key)
            return hit[0]

    def _admit(self, key, host: np.ndarray, hit_stat: str) -> Optional[object]:
        """Install ``host`` on device OUTSIDE the lock, charge it to the
        ledger and keep it under ``key``; None if the ledger refuses it."""
        import jax

        dev = self.install(host)
        jax.block_until_ready(dev)
        nbytes = int(getattr(dev, "nbytes", host.nbytes))
        region = None
        if self._memory is not None:
            # reserve BEFORE taking the cache lock: admission may run the
            # reclaim ladder, whose device-image rung locks this cache
            try:
                region = self._memory.reserve(
                    nbytes, KIND_DEVICE_IMAGE,
                    owner="/".join(map(str, key[:2])), block=False,
                )
            except MemoryPressureError:
                return None  # caller falls back to the host path
            region.commit()
        evicted = []
        with self._lock:
            raced = self._entries.get(key)
            if raced is not None:  # lost a build race: keep the winner
                self.stats[hit_stat] += 1
                if region is not None:
                    evicted.append(region)
                dev = raced[0]
            else:
                self.stats["misses"] += 1
                self.stats["built_bytes"] += nbytes
                self._entries[key] = (dev, nbytes)
                self.total_bytes += nbytes
                if region is not None:
                    self._regions[key] = region
                evicted.extend(self._evict_capacity())
        for r in evicted:
            r.release()
        return dev

    def note_base_served(self, nbytes: int) -> None:
        """Fused restores report BASE bytes materialized from device-resident
        pages (the device-tier analogue of the host cache's counter)."""
        with self._lock:
            self.stats["base_bytes_served"] += nbytes

    def resident_bytes(self) -> int:
        with self._lock:
            return self.total_bytes

    def resident_entries(self) -> int:
        with self._lock:
            return len(self._entries)

    # ------------------------------------------------------------- eviction
    def _drop(self, key) -> int:
        with self._lock:
            entry = self._entries.pop(key, None)
            if entry is None:
                return 0
            self.total_bytes -= entry[1]
            self.stats["evictions"] += 1
            return entry[1]

    def _evict_capacity(self):
        """Capacity LRU (under self._lock); returns regions to release once
        the lock drops (lock order is always cache -> manager)."""
        released = []
        while self.total_bytes > self.capacity and len(self._entries) > 1:
            key, (_dev, nbytes) = self._entries.popitem(last=False)
            self.total_bytes -= nbytes
            self.stats["evictions"] += 1
            region = self._regions.pop(key, None)
            if region is not None:
                released.append(region)
        return released

    def reclaim(self, nbytes: int, protect=frozenset()) -> int:
        """Ladder rung 1: LRU-evict device base pages until ``nbytes`` are
        freed.  Every entry is recoverable (one re-upload from the host
        base image), so the rung may drain the cache entirely."""
        freed = 0
        released = []
        with self._lock:
            while self._entries and freed < nbytes:
                key, (_dev, ebytes) = self._entries.popitem(last=False)
                self.total_bytes -= ebytes
                self.stats["evictions"] += 1
                freed += ebytes
                region = self._regions.pop(key, None)
                if region is not None:
                    released.append(region)
        for r in released:
            r.release()
        return freed

    def snapshot_stats(self) -> Dict[str, int]:
        with self._lock:
            return dict(self.stats)


@dataclasses.dataclass
class DevicePath:
    """The device-restore bundle a :class:`SpiceRestorer` takes as its
    ``device_path=`` mode: the node's shared upload ring and the HBM base
    cache (None disables fused patching — every tensor full-uploads)."""

    upload: UploadStream
    images: Optional[DeviceImageCache] = None
