"""Device-restore fast path: UploadStream, DeviceImageCache, the fused
restore's equality with the eager path, install-policy selection on the
node, and the device-resident re-restore economics."""
import gc
import threading
import time
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (
    BaseImage,
    NodeImageCache,
    NodeMemoryManager,
    SpiceRestorer,
    snapshot,
)
from repro.core.restore import RestoreStats, TensorHandle
from repro.core.treeutil import flatten_state
from repro.core.upload import DeviceImageCache, DevicePath, UploadStream
from repro.models import lm
from repro.serve.engine import ServerlessNode, layerwise_state
from repro.serve.instance import InstanceState

ARCH = "qwen1.5-0.5b"
PROMPT = np.array([[3, 1, 4, 1, 5, 9]], dtype=np.int32)


# ------------------------------------------------------------ UploadStream
def test_upload_stream_full_upload_and_flush():
    up = UploadStream(depth=2, name="t-up")
    try:
        handles = []
        keep = []  # buffers must outlive the async jobs
        for i in range(5):
            h = TensorHandle(f"t{i}", (256,), "float32")
            buf = np.zeros(2048, np.uint8)
            buf[:1024] = np.frombuffer(
                np.full(256, float(i), np.float32).tobytes(), np.uint8
            )
            up.upload_full(h, buf, shape=(256,), dtype="float32", nbytes=1024)
            handles.append(h)
            keep.append(buf)
        assert up.flush(timeout=30)
        for i, h in enumerate(handles):
            arr = h.wait(timeout=5)
            assert np.all(np.asarray(arr) == float(i))
        st = up.snapshot_stats()
        assert st["uploads"] == 5
        assert st["uploaded_bytes"] == 5 * 1024
        assert st["failures"] == 0
    finally:
        up.close()
    up.close()  # idempotent


def test_upload_stream_release_called_after_upload_lands():
    """Staging buffers return to their release hook only once the device
    copy finished — the pool re-zeroes them, so an early release would
    corrupt the transfer."""
    released = []
    done = threading.Event()

    def release(buf):
        released.append(buf)
        done.set()

    up = UploadStream(depth=1)
    try:
        h = TensorHandle("t", (16,), "float32")
        buf = np.frombuffer(
            np.arange(16, dtype=np.float32).tobytes(), np.uint8
        ).copy()
        up.upload_full(h, buf, shape=(16,), dtype="float32", nbytes=64,
                       release=release)
        arr = h.wait(timeout=10)
        np.testing.assert_array_equal(
            np.asarray(arr), np.arange(16, dtype=np.float32)
        )
        assert done.wait(10)
        assert released and released[0] is buf
    finally:
        up.close()


def test_upload_stream_failure_fails_handle():
    def broken_install(arr):
        raise RuntimeError("device OOM")

    up = UploadStream(install=broken_install)
    try:
        h = TensorHandle("t", (4,), "float32")
        up.upload_full(h, np.zeros(16, np.uint8), shape=(4,),
                       dtype="float32", nbytes=16)
        with pytest.raises(RuntimeError, match="restore of t failed"):
            h.wait(timeout=10)
        assert up.flush(timeout=10)
        assert up.snapshot_stats()["failures"] == 1
    finally:
        up.close()
    with pytest.raises(RuntimeError, match="closed"):
        up.upload_full(TensorHandle("x", (1,), "float32"),
                       np.zeros(4, np.uint8), shape=(1,),
                       dtype="float32", nbytes=4)


class _InFlight:
    """A device array whose transfer lands once ``landed`` is set (or its
    deadline passes); ``block_until_ready`` waits for that, as a real
    transfer's does."""

    def __init__(self, arr, landed=None, deadline=None, error=None):
        self.arr, self.landed, self.deadline, self.error = arr, landed, deadline, error

    def block_until_ready(self):
        if self.landed is not None:
            assert self.landed.wait(10)
        if self.deadline is not None:
            time.sleep(max(0.0, self.deadline - time.perf_counter()))
        if self.error is not None:
            raise self.error
        return self


class _OrderedHandle(TensorHandle):
    def __init__(self, name, order):
        super().__init__(name, (4,), "float32")
        self.order = order

    def set(self, arr):
        self.order.append(self.name)
        super().set(arr)


def _gated_ring(n, depth, errors=()):
    """A ring whose i-th install lands when ``gates[i]`` is set; returns
    the ring, the gates, the installs issued so far, and submit(i)."""
    gates = [threading.Event() for _ in range(n)]
    issued = []

    def install(view):
        i = len(issued)
        issued.append(i)
        err = RuntimeError(f"transfer {i} lost") if i in errors else None
        return _InFlight(np.array(view), landed=gates[i], error=err)

    up = UploadStream(depth=depth, name="t-gated", install=install)
    return up, gates, issued


def _submit(up, name, order, release=None, value=0.0, stats=None):
    h = _OrderedHandle(name, order)
    buf = np.frombuffer(np.full(4, value, np.float32).tobytes(), np.uint8).copy()
    up.upload_full(h, buf, shape=(4,), dtype="float32", nbytes=16,
                   release=release, stats=stats)
    return h, buf


def _wait_for(pred, timeout=10.0):
    t_end = time.perf_counter() + timeout
    while not pred():
        assert time.perf_counter() < t_end
        time.sleep(0.002)


def test_upload_stream_issues_next_job_before_last_lands():
    """Job k+1 is issued while job k is still in flight; handles still
    resolve in submit order, only once their own job landed."""
    up, gates, issued = _gated_ring(4, depth=4)
    order = []
    try:
        handles = [_submit(up, f"t{i}", order, value=float(i))[0] for i in range(4)]
        _wait_for(lambda: len(issued) == 4)  # all four issued, none landed
        assert not any(h.ready for h in handles)
        for i in (3, 2, 1):  # later jobs land first: nothing resolves yet
            gates[i].set()
        time.sleep(0.05)
        assert not any(h.ready for h in handles)
        gates[0].set()
        assert up.flush(timeout=10)
        assert order == ["t0", "t1", "t2", "t3"]
        for i, h in enumerate(handles):
            assert np.all(h.wait(timeout=5).arr.view(np.float32) == float(i))
        st = up.snapshot_stats()
        assert st["in_flight_max"] >= 2
        assert st["issued_while_busy"] > 0
        assert st["uploads"] == 4 and st["failures"] == 0
    finally:
        for g in gates:
            g.set()
        up.close()


def test_upload_stream_depth_bounds_jobs_not_landed():
    """``submit`` blocks while ``depth`` jobs are outstanding, in flight
    or queued."""
    up, gates, issued = _gated_ring(3, depth=2)
    order = []
    try:
        _submit(up, "t0", order)
        _submit(up, "t1", order)
        third = threading.Thread(target=_submit, args=(up, "t2", order))
        third.start()
        _wait_for(lambda: len(issued) == 2)
        time.sleep(0.05)
        assert third.is_alive() and len(issued) == 2
        gates[0].set()
        third.join(10)
        assert not third.is_alive()
        gates[1].set()
        gates[2].set()
        assert up.flush(timeout=10)
        assert up.snapshot_stats()["in_flight_max"] == 2
    finally:
        for g in gates:
            g.set()
        up.close()


def test_upload_stream_releases_each_buffer_after_its_own_job_lands():
    """With several jobs in flight, each staging buffer returns to the
    pool only after the transfer that reads it landed."""
    up, gates, issued = _gated_ring(4, depth=4)
    order, released = [], []

    def release(buf):
        i = next(k for k, b in enumerate(bufs) if b is buf)
        released.append((i, gates[i].is_set()))

    bufs = []
    try:
        for i in range(4):
            bufs.append(_submit(up, f"t{i}", order, release=release)[1])
        _wait_for(lambda: len(issued) == 4)
        time.sleep(0.05)
        assert released == []
        for i in (2, 0, 3, 1):
            gates[i].set()
        assert up.flush(timeout=10)
        assert released == [(0, True), (1, True), (2, True), (3, True)]
    finally:
        for g in gates:
            g.set()
        up.close()


def test_upload_stream_landing_failure_fails_only_its_handle():
    up, gates, _issued = _gated_ring(3, depth=3, errors={1})
    order, released = [], []
    try:
        handles = [_submit(up, f"t{i}", order, release=released.append,
                           value=float(i))[0] for i in range(3)]
        for g in gates:
            g.set()
        assert up.flush(timeout=10)
        with pytest.raises(RuntimeError, match="restore of t1 failed"):
            handles[1].wait(timeout=5)
        assert handles[0].wait(timeout=5).arr.view(np.float32)[0] == 0.0
        assert handles[2].wait(timeout=5).arr.view(np.float32)[0] == 2.0
        assert len(released) == 3  # the failed job's buffer too
        st = up.snapshot_stats()
        assert st["failures"] == 1 and st["uploads"] == 2
    finally:
        up.close()


def test_upload_stream_close_drains_jobs_in_flight():
    up, gates, issued = _gated_ring(3, depth=3)
    order, released = [], []
    handles = [_submit(up, f"t{i}", order, release=released.append)[0]
               for i in range(3)]
    _wait_for(lambda: len(issued) == 3)
    opener = threading.Timer(0.1, lambda: [g.set() for g in gates])
    opener.start()
    up.close(timeout=10)
    opener.join()
    assert order == ["t0", "t1", "t2"] and len(released) == 3
    assert all(h.ready for h in handles)
    assert not any(th.is_alive() for th in up._threads)


def test_upload_stream_upload_time_is_the_union_of_overlapping_jobs():
    """Jobs in flight together add their ring time once: the stream's and
    the restore's ``upload_s`` stay within the burst's wall time."""
    land_s = 0.05

    def install(view):
        return _InFlight(np.array(view), deadline=time.perf_counter() + land_s)

    up = UploadStream(depth=8, install=install)
    stats = RestoreStats()
    order = []
    try:
        t0 = time.perf_counter()
        for i in range(8):
            _submit(up, f"t{i}", order, stats=stats)
        assert up.flush(timeout=10)
        wall = time.perf_counter() - t0
        st = up.snapshot_stats()
        assert land_s <= st["upload_s"] <= wall < 8 * land_s
        assert stats.upload_s == pytest.approx(st["upload_s"])
        assert st["issued_while_busy"] > 0
    finally:
        up.close()


# -------------------------------------------------------- DeviceImageCache
def _base_image(name="b", n_pages=4, page_bytes=512, seed=0):
    page_elems = page_bytes // 4
    raw = np.random.RandomState(seed).randn(
        n_pages * page_elems
    ).astype(np.float32)
    return BaseImage.from_state(name, {"w": raw}, page_size=page_bytes), raw


def test_device_image_cache_ledger_charge_and_reclaim_rung():
    base, raw = _base_image()
    mem = NodeMemoryManager(64 << 20)
    cache = DeviceImageCache()
    cache.attach(mem)
    pages = cache.get_pages(base, "w", 4, 128, np.float32)
    assert pages is not None
    np.testing.assert_array_equal(
        np.asarray(pages).reshape(-1), raw
    )
    assert mem.kind_bytes()["device_image"] == cache.resident_bytes() > 0
    mem.audit()
    # second lookup hits without rebuilding
    again = cache.get_pages(base, "w", 4, 128, np.float32)
    assert again is pages
    st = cache.snapshot_stats()
    assert st["hits"] == 1 and st["misses"] == 1
    # the reclaim rung drains the cache and uncharges the ledger
    freed = cache.reclaim(1 << 30)
    assert freed == st["built_bytes"]
    assert cache.resident_entries() == 0
    assert mem.kind_bytes()["device_image"] == 0
    mem.audit()


def test_device_image_cache_mismatch_returns_none():
    base, _ = _base_image(page_bytes=512)
    cache = DeviceImageCache()
    # page geometry disagrees with the base's page size -> host fallback
    assert cache.get_pages(base, "w", 4, 64, np.float32) is None
    # tensor absent from the base -> host fallback
    assert cache.get_pages(base, "nope", 4, 128, np.float32) is None


def test_device_image_cache_pressure_falls_back():
    base, _ = _base_image()
    mem = NodeMemoryManager(1024)  # far too small for the 8 KB of pages
    cache = DeviceImageCache()
    cache.attach(mem)
    assert cache.get_pages(base, "w", 4, 128, np.float32) is None
    assert mem.kind_bytes()["device_image"] == 0
    mem.audit()


def test_device_image_cache_tensor_entry_and_mismatches():
    base, raw = _base_image()
    mem = NodeMemoryManager(64 << 20)
    cache = DeviceImageCache()
    cache.attach(mem)
    arr = cache.get_tensor(base, "w", raw.shape, np.float32)
    np.testing.assert_array_equal(np.asarray(arr), raw)
    assert arr.shape == raw.shape and arr.dtype == raw.dtype
    assert cache.get_tensor(base, "w", raw.shape, np.float32) is arr
    # the tensor entry is its own, never the kernel's pages
    pages = cache.get_pages(base, "w", 4, 128, np.float32)
    assert pages is not arr
    st = cache.snapshot_stats()
    assert st["tensor_hits"] == 1 and st["hits"] == 0 and st["misses"] == 2
    assert mem.kind_bytes()["device_image"] == cache.resident_bytes() == 2 * raw.nbytes
    # shape, dtype or length other than the base's, or an absent tensor
    assert cache.get_tensor(base, "w", (4, 128), np.float32) is None
    assert cache.get_tensor(base, "w", raw.shape, np.int32) is None
    assert cache.get_tensor(base, "w", (raw.size - 1,), np.float32) is None
    assert cache.get_tensor(base, "nope", raw.shape, np.float32) is None
    assert cache.reclaim(1 << 30) == 2 * raw.nbytes
    assert mem.kind_bytes()["device_image"] == 0
    mem.audit()


def test_device_image_cache_tensor_pressure_falls_back():
    base, raw = _base_image()
    mem = NodeMemoryManager(1024)  # far too small for the 8 KB tensor
    cache = DeviceImageCache()
    cache.attach(mem)
    assert cache.get_tensor(base, "w", raw.shape, np.float32) is None
    assert mem.kind_bytes()["device_image"] == 0
    mem.audit()


# ------------------------------------------------- fused restore equality
def test_fused_delta_restore_matches_eager(tmp_path):
    ps = 512
    rng = np.random.RandomState(5)
    base_st = {
        "w0": rng.randn(4 * (ps // 4)).astype(np.float32),
        "w1": rng.randn(3 * (ps // 4) + 7).astype(np.float32),  # tail page
    }
    ft = {k: v.copy() for k, v in base_st.items()}
    ft["w0"][: ps // 4] += 1.0  # one dirty page each
    ft["w1"][: ps // 4] += 1.0
    parent = str(tmp_path / "p.jif")
    delta = str(tmp_path / "d.jif")
    snapshot(base_st, parent, page_size=ps)
    snapshot(ft, delta, parent=parent, page_size=ps)

    cache = NodeImageCache()
    r_ref = SpiceRestorer(
        node_cache=cache, transform=lambda a: jnp.array(a, copy=True)
    )
    ref_state, _, _, ref_stats = r_ref.restore(delta)
    r_ref.iosched.shutdown()

    up = UploadStream()
    dpath = DevicePath(upload=up, images=DeviceImageCache())
    r = SpiceRestorer(node_cache=cache, device_path=dpath)
    state, _, handles, st = r.restore(delta, wait=True)
    r.iosched.shutdown()
    up.close()

    l_ref, _ = flatten_state(ref_state)
    l_fused, _ = flatten_state(state)
    for (n1, a), (n2, b) in zip(l_ref, l_fused):
        assert n1 == n2
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=n1)
    # the fused tensors are real device arrays, not host staging views
    for h in handles.values():
        assert isinstance(h._arr, jax.Array)
    # only the private pages crossed to device; the patch covered the rest
    assert st.uploaded_bytes == 2 * ps
    assert st.uploaded_bytes < ref_stats.bytes_read + ref_stats.base_bytes
    assert st.patched_on_device_bytes == sum(a.nbytes for a in ft.values())
    assert st.bytes_read == 2 * ps  # reads also shrank to the private runs


def test_restored_tree_is_freed_without_the_cycle_collector(tmp_path):
    """Once a restore completed, only its caller holds its tensors: with the
    cycle collector off, dropping the tree frees them, so a cold restore's
    device tree does not stay in HBM until the next collection."""
    path = str(tmp_path / "f.jif")
    snapshot({"w0": np.arange(512, dtype=np.float32),
              "w1": np.ones(300, np.float32)}, path, page_size=512)
    up = UploadStream()
    r = SpiceRestorer(node_cache=NodeImageCache(),
                      device_path=DevicePath(upload=up, images=DeviceImageCache()))
    gc.disable()
    try:
        state, _, handles, st = r.restore(path, wait=True)
        assert st.wait_complete(10)
        refs = [weakref.ref(h) for h in handles.values()]
        del state, handles
        _wait_for(lambda: all(ref() is None for ref in refs))
    finally:
        gc.enable()
        r.iosched.shutdown()
        up.close()


# --------------------------------------------------- node install policies
@pytest.fixture(scope="module")
def policy_zoo(tmp_path_factory):
    d = tmp_path_factory.mktemp("policy-zoo")
    cfg = get_config(ARCH).reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(2), jnp.float32)
    return d, cfg, params


def _publish(node, d, cfg, params, extra=None):
    base_key = "pol-base"
    node.node_cache.put(
        BaseImage.from_state(base_key, layerwise_state(cfg, params)),
        evictable=False,
    )
    tuned = dict(params)
    tuned["final_norm"] = tuned["final_norm"] + 0.01
    node.publish("pol-fn", cfg, tuned, str(d), base_name=base_key,
                 formats=("jif",), warm_ttl_s=60, extra_state=extra)


@pytest.mark.parametrize("install", ["host", "eager", "fused"])
def test_install_policy_end_to_end(policy_zoo, install, tmp_path):
    d, cfg, params = policy_zoo
    node = ServerlessNode(install=install)
    try:
        _publish(node, tmp_path, cfg, params)
        r = node.invoke("pol-fn", PROMPT, max_new_tokens=3, cfg=cfg)
        assert r.cold
        assert node.scheduler.drain_residual()
        node.memory.audit()
        # every policy generates the same tokens
        node.evict()
        r2 = node.invoke("pol-fn", PROMPT, max_new_tokens=3, cfg=cfg)
        np.testing.assert_array_equal(r.tokens, r2.tokens)
    finally:
        node.close()


def test_install_policy_callable_and_invalid(policy_zoo):
    _d, cfg, _params = policy_zoo
    calls = []

    def spy(a):
        calls.append(a.nbytes)
        return jnp.array(a, copy=True)

    node = ServerlessNode(install=spy)
    try:
        transform, dpath = node.scheduler._install_policy()
        assert transform is spy and dpath is None
    finally:
        node.close()
    node = ServerlessNode(install="host")
    try:
        transform, dpath = node.scheduler._install_policy()
        assert transform is None and dpath is None
        assert node.scheduler.upload_stream is None
    finally:
        node.close()
    node = ServerlessNode(install="fused")
    try:
        transform, dpath = node.scheduler._install_policy()
        assert transform is None
        assert dpath.upload is node.scheduler.upload_stream
        assert dpath.images is node.scheduler.device_images
        node.scheduler.install = "bogus"
        with pytest.raises(ValueError, match="bogus"):
            node.scheduler._install_policy()
    finally:
        node.close()


# ------------------------------------ device-resident re-restore economics
def test_residual_evict_rerestore_keeps_device_base(policy_zoo, tmp_path):
    """Regression: a residual-evicted instance re-restored under the fused
    policy must read exactly the dropped residual bytes, serve its working
    set from the pinned memory (zero re-uploads for it), and reuse the
    HBM-resident device base without rebuilding a single entry."""
    _d, cfg, params = policy_zoo
    node = ServerlessNode(install="fused")
    try:
        extra = {"opt": np.ones((1 << 20,), np.float32)}  # 4 MB residual
        _publish(node, tmp_path, cfg, params, extra=extra)
        r1 = node.invoke("pol-fn", PROMPT, max_new_tokens=3, cfg=cfg)
        assert r1.cold
        assert node.scheduler.drain_residual()
        inst = node.scheduler.instance("pol-fn")
        residual_bytes = inst.residual_region.nbytes
        images = node.scheduler.device_images
        mid = images.snapshot_stats()
        assert images.resident_bytes() > 0  # base pages live in HBM

        freed = node.scheduler.evict_residual("pol-fn")
        assert freed == residual_bytes
        assert inst.state is InstanceState.EVICTED
        node.memory.audit()
        up_before = node.scheduler.upload_stream.snapshot_stats()

        r2 = node.invoke("pol-fn", PROMPT, max_new_tokens=3, cfg=cfg)
        assert r2.cold
        assert node.scheduler.drain_residual()
        d2 = inst.restore_stats.as_dict()
        # reads: exactly the dropped residual (chunk-padded per tensor)
        assert d2["reused_bytes"] > 0
        assert d2["bytes_read"] <= residual_bytes + 4096 * d2["residual_tensors"]
        # uploads: only the residual tensors crossed again — bounded by the
        # bytes re-read plus zero-page patches, nowhere near the image size
        up_after = node.scheduler.upload_stream.snapshot_stats()
        uploaded = up_after["uploaded_bytes"] - up_before["uploaded_bytes"]
        assert uploaded <= residual_bytes + 4096 * d2["residual_tensors"]
        # the device base was NOT rebuilt: no new cache builds (misses)
        after = images.snapshot_stats()
        assert after["misses"] == mid["misses"]
        np.testing.assert_array_equal(r1.tokens, r2.tokens)
        node.memory.audit()
    finally:
        node.close()


# ------------------------------------- unchanged tensors share the HBM base
PS = 512


def _delta_restore(tmp_path, base_st, ft, *, node_base=None, memory=None):
    """Restore the delta ``ft`` of ``base_st`` through a fresh DevicePath;
    ``node_base`` (a state) stands in for the parent in the node cache,
    ``memory`` is the ledger the device-image cache charges."""
    from repro.core.lifecycle import parent_cache_key

    parent = str(tmp_path / "p.jif")
    delta = str(tmp_path / "d.jif")
    snapshot(base_st, parent, page_size=PS)
    snapshot(ft, delta, parent=parent, page_size=PS)
    cache = NodeImageCache()
    if node_base is not None:
        cache.put(BaseImage.from_state(parent_cache_key(parent), node_base,
                                       page_size=PS), evictable=False)
    images = DeviceImageCache()
    if memory is not None:
        images.attach(memory)
    up = UploadStream()
    r = SpiceRestorer(node_cache=cache, device_path=DevicePath(upload=up, images=images))
    try:
        state, _, _, st = r.restore(delta, wait=True)
        assert st.wait_complete(10)
    finally:
        r.iosched.shutdown()
        up.close()
    return state, st, up.snapshot_stats(), cache.get(parent_cache_key(parent)), images


def _tensor_entries(images):
    return [dev for key, (dev, _) in images._entries.items() if len(key) == 4]


def test_unchanged_tensor_is_the_shared_base_array(tmp_path, monkeypatch):
    from repro.kernels.overlay_patch import ops

    calls = []
    real = ops.overlay_patch_device

    def counted(*a):
        calls.append(a[2])
        return real(*a)

    monkeypatch.setattr(ops, "overlay_patch_device", counted)
    rng = np.random.RandomState(7)
    base_st = {k: rng.randn(4 * (PS // 4)).astype(np.float32) for k in "abc"}
    ft = {k: v.copy() for k, v in base_st.items()}
    ft["b"][: PS // 4] += 1.0  # mixed: one private page, three BASE
    ft["c"] += 1.0  # every page private
    state, st, ring, base, images = _delta_restore(tmp_path, base_st, ft)
    for k in "abc":
        np.testing.assert_array_equal(np.asarray(state[k]), ft[k], err_msg=k)
    # the unchanged leaf IS the cache's array, and no other leaf is
    assert state["a"] is images.get_tensor(base, "a", (PS,), np.float32)
    assert not any(state[k] is dev for k in "bc" for dev in _tensor_entries(images))
    # the ring carried only the mixed (fused) and the all-private tensors
    assert ring["uploads"] == 2 and ring["fused_patches"] == 1
    assert st.shared_tensors == 1 and st.shared_bytes == ft["a"].nbytes
    assert st.as_dict()["shared_tensors"] == 1
    assert st.patched_on_device_bytes == ft["b"].nbytes
    assert st.uploaded_bytes == PS + ft["c"].nbytes
    assert st.base_bytes == ft["a"].nbytes + 3 * PS
    assert images.snapshot_stats()["base_bytes_served"] == st.base_bytes
    assert len(calls) == 1  # the kernel still runs for the mixed tensor


@pytest.mark.parametrize("case", ["private_page", "shape", "dtype", "length", "ledger"])
def test_tensors_the_base_cannot_serve_take_the_ring(tmp_path, case):
    """A tensor is shared only when every page is BASE and the base holds
    it at the same shape, dtype and length, and the ledger admits it;
    otherwise it restores on today's path, to the same leaf."""
    raw = np.random.RandomState(3).randn(4 * (PS // 4)).astype(np.float32)
    ft = {"w": raw.copy()}
    node_base = memory = None
    if case == "private_page":
        ft["w"][-1] += 1.0
    elif case == "shape":
        node_base = {"w": raw.reshape(4, PS // 4)}
    elif case == "dtype":
        node_base = {"w": raw.view(np.int32)}
    elif case == "length":
        node_base = {"w": np.concatenate([raw, np.ones(PS // 4, np.float32)])}
    else:
        memory = NodeMemoryManager(1024)  # refuses every device entry
    state, st, ring, _base, images = _delta_restore(
        tmp_path, {"w": raw}, ft, node_base=node_base, memory=memory)
    np.testing.assert_array_equal(np.asarray(state["w"]), ft["w"])
    assert isinstance(state["w"], jax.Array)
    assert st.shared_tensors == 0 and st.shared_bytes == 0
    assert not any(state["w"] is dev for dev in _tensor_entries(images))
    assert ring["uploads"] == 1
    assert ring["fused_patches"] == (0 if case == "ledger" else 1)
    if memory is not None:
        memory.audit()


def test_shared_base_arrays_outlive_instances_and_the_cache_entry(policy_zoo, tmp_path):
    """On a fused node the unchanged leaves of a restored function are the
    device-image cache's arrays: evicting the instance deletes none of
    them, the reclaim rung dropping the entries leaves a live instance's
    tree intact and the ledger balanced, and a second fine-tune of the
    base reuses the entries instead of building them again."""
    _d, cfg, params = policy_zoo
    node = ServerlessNode(install="fused")
    try:
        base = BaseImage.from_state("pol-base", layerwise_state(cfg, params))
        node.node_cache.put(base, evictable=False)
        for i, fn in enumerate(("pol-fn", "pol-fn2")):
            tuned = dict(params)
            tuned["final_norm"] = tuned["final_norm"] + 0.01 * (i + 1)
            node.publish(fn, cfg, tuned, str(tmp_path), base_name="pol-base",
                         formats=("jif",), warm_ttl_s=60,
                         extra_state={"opt": np.ones((1 << 16,), np.float32)})
        images = node.scheduler.device_images
        r = node.invoke("pol-fn", PROMPT, max_new_tokens=3, cfg=cfg)
        assert r.cold
        assert node.scheduler.drain_residual()
        inst = node.scheduler.instance("pol-fn")
        stats = inst.restore_stats
        assert stats.shared_tensors > 0
        entries = _tensor_entries(images)
        shared = [a for a in jax.tree.leaves(inst.tree)
                  if any(a is dev for dev in entries)]
        assert len(shared) == stats.shared_tensors == len(entries)
        host = [np.asarray(a) for a in shared]
        warm = node.invoke("pol-fn", PROMPT, max_new_tokens=3, cfg=cfg)
        assert not warm.cold
        np.testing.assert_array_equal(r.tokens, warm.tokens)

        # the rung drops the entries while the instance holds the arrays
        mid = images.snapshot_stats()
        assert images.reclaim(1 << 40) > 0 and images.resident_entries() == 0
        node.memory.audit()
        assert all(not a.is_deleted() for a in shared)
        again = node.invoke("pol-fn", PROMPT, max_new_tokens=3, cfg=cfg)
        assert not again.cold
        np.testing.assert_array_equal(r.tokens, again.tokens)

        # a second fine-tune's restore builds the entries once more, then a
        # third restore of it hits them
        r2 = node.invoke("pol-fn2", PROMPT, max_new_tokens=3, cfg=cfg)
        assert r2.cold
        assert node.scheduler.drain_residual()
        built = images.snapshot_stats()
        assert built["misses"] == mid["misses"] + stats.shared_tensors
        node.evict("pol-fn2")
        r3 = node.invoke("pol-fn2", PROMPT, max_new_tokens=3, cfg=cfg)
        assert r3.cold
        assert node.scheduler.drain_residual()
        after = images.snapshot_stats()
        assert after["tensor_hits"] == built["tensor_hits"] + stats.shared_tensors
        assert after["misses"] == built["misses"]
        np.testing.assert_array_equal(r2.tokens, r3.tokens)

        # evicting the instances deletes no shared array
        assert node.scheduler.evict_residual("pol-fn") > 0
        node.evict()
        node.memory.audit()
        for a, h in zip(shared, host):
            assert not a.is_deleted()
            np.testing.assert_array_equal(np.asarray(a), h)
        for dev in _tensor_entries(images):
            assert not dev.is_deleted()
    finally:
        node.close()
