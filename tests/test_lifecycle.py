"""Snapshot lifecycle subsystem: JIF v2 format compatibility (golden v1
bytes), delta chains, two-phase working-set restore, concurrent itable
loads, and the serving-side WARM-at-working-set promotion + record →
relayout feedback loop."""
import os
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from repro.core import (
    BaseImage,
    NodeImageCache,
    SnapshotPipeline,
    SpiceRestorer,
    snapshot,
)
from repro.core.jif import JifReader
from repro.core.lifecycle import parent_cache_key
from repro.core.treeutil import flatten_state

PAGE = 4096
GOLDEN = Path(__file__).parent / "golden" / "jif_v1_small.jif"


def golden_state():
    """Deterministic state matching the checked-in v1 golden image (written
    by the pre-pipeline writer)."""
    r = np.random.RandomState(42)
    return {
        "embed": {"tok": r.randn(64, 32).astype(np.float32)},
        "layers": [
            {"w": r.randn(32, 48).astype(np.float32),
             "b": np.zeros((2048,), np.float32)}
            for _ in range(3)
        ],
        "step": np.int64(11),
    }


def rng_state(seed=0, scale=1):
    r = np.random.RandomState(seed)
    return {
        "embed": {"tok": r.randn(64 * scale, 32).astype(np.float32)},
        "layers": [
            {"w": r.randn(32, 64).astype(np.float32),
             "b": np.zeros((2048,), np.float32)}
            for _ in range(3)
        ],
        "step": np.int64(7),
    }


def assert_state_equal(a, b):
    la, _ = flatten_state(a)
    lb, _ = flatten_state(b)
    assert [n for n, _ in la] == [n for n, _ in lb]
    for (n, x), (_, y) in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y), err_msg=n)


# ------------------------------------------------------- format compatibility
def test_golden_v1_restores_byte_identically():
    """A v1 JIF written by the pre-pipeline writer still restores, byte for
    byte, through the v2 reader."""
    got, meta, _, _ = SpiceRestorer().restore(str(GOLDEN))
    assert_state_equal(golden_state(), got)
    assert meta["golden"] == "v1"


def test_golden_v1_header_defaults():
    with JifReader(str(GOLDEN)) as r:
        assert r.version == 1
        assert not r.has_digests
        assert r.digests("embed/tok") is None
        # no boundary recorded: the whole data segment is the working set
        assert r.ws_boundary == r.n_data_chunks
        assert r.parent is None


def test_v2_header_carries_boundary_and_digests(tmp_path):
    state = rng_state()
    names = [n for n, _ in flatten_state(state)[0]]
    path = str(tmp_path / "f.jif")
    stats = snapshot(state, path, access_order=names, working_set=names[:2],
                     page_size=PAGE)
    with JifReader(path) as r:
        assert r.version == 2
        assert r.has_digests
        assert 0 < r.ws_boundary < r.n_data_chunks
        assert r.ws_boundary == stats.ws_boundary
        assert r.meta["working_set"] == names[:2]
        # stored digests match a fresh hash of the source bytes
        from repro.core import overlay

        raw = np.ascontiguousarray(state["embed"]["tok"]).view(np.uint8).reshape(-1)
        np.testing.assert_array_equal(
            r.digests("embed/tok"),
            overlay.chunk_digests(memoryview(raw), PAGE),
        )


def test_concurrent_itable_loads_one_reader(tmp_path):
    """Regression: itable loads used seek+read on the shared fd; many
    scheduler threads hitting one reader must still see correct tables."""
    state = {f"t{i:02d}": np.full((97 + 13 * i,), i, np.float32) for i in range(40)}
    path = str(tmp_path / "many.jif")
    snapshot(state, path, page_size=256)

    expect = {}
    with JifReader(path) as ref:
        for t in ref.tensors:
            expect[t.name] = ref.itable(t.name).table.copy()

    shared = JifReader(path)
    errors = []

    def worker(seed):
        r = np.random.RandomState(seed)
        names = list(expect)
        r.shuffle(names)
        for name in names:
            got = shared.itable(name).table
            if not np.array_equal(got, expect[name]):
                errors.append(name)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    shared.close()
    assert not errors


# ----------------------------------------------------------------- delta chain
def test_delta_chain_roundtrip(tmp_path):
    """parent → child → grandchild, restored through the chain from a COLD
    cache (parents bootstrapped from disk)."""
    parent = rng_state(5)
    parent_path = str(tmp_path / "parent.jif")
    full = snapshot(parent, parent_path, page_size=PAGE)

    child = rng_state(5)
    child["layers"][0]["w"] = child["layers"][0]["w"] + 1.0
    child_path = str(tmp_path / "child.jif")
    cs = snapshot(child, child_path, parent=parent_path, page_size=PAGE)
    assert cs.private_bytes < 0.4 * full.private_bytes  # only dirty pages
    assert cs.base_bytes > 0
    assert cs.parent == os.path.abspath(parent_path)

    grand = dict(child)
    grand["embed"] = {"tok": child["embed"]["tok"] * 1.5}
    grand_path = str(tmp_path / "grand.jif")
    snapshot(grand, grand_path, parent=child_path, page_size=PAGE)

    cache = NodeImageCache()
    got, _, _, rstats = SpiceRestorer(node_cache=cache).restore(grand_path)
    assert_state_equal(grand, got)
    # both ancestors were bootstrapped into the node cache from disk
    assert cache.get(parent_cache_key(parent_path)) is not None
    assert cache.get(parent_cache_key(child_path)) is not None


def test_delta_against_v1_parent(tmp_path):
    """A v1 parent (no stored digests) is materialized once and still
    serves as a delta base."""
    child = golden_state()
    child["layers"][2]["w"] = child["layers"][2]["w"] + 2.0
    child_path = str(tmp_path / "child.jif")
    stats = snapshot(child, child_path, parent=str(GOLDEN), page_size=PAGE)
    assert stats.base_bytes > 0
    got, _, _, _ = SpiceRestorer(node_cache=NodeImageCache()).restore(child_path)
    assert_state_equal(child, got)


def test_rewritten_parent_fails_loudly(tmp_path):
    """A parent rewritten in place after the delta was written must fail the
    restore (key mismatch), never serve stale/new parent bytes silently."""
    parent_path = str(tmp_path / "p.jif")
    snapshot(rng_state(5), parent_path, page_size=PAGE)
    child = rng_state(5)
    child["layers"][0]["w"] = child["layers"][0]["w"] + 1.0
    child_path = str(tmp_path / "c.jif")
    snapshot(child, child_path, parent=parent_path, page_size=PAGE)

    time.sleep(0.01)  # distinct mtime_ns for the rewrite
    snapshot(rng_state(6), parent_path, page_size=PAGE)  # in-place rewrite
    with pytest.raises(FileNotFoundError, match="changed on disk"):
        SpiceRestorer(node_cache=NodeImageCache()).restore(child_path)


def test_base_image_from_jif_matches_from_state(tmp_path):
    state = rng_state(9)
    path = str(tmp_path / "f.jif")
    snapshot(state, path, page_size=PAGE)
    img = BaseImage.from_jif(path, name="img")
    ref = BaseImage.from_state("img", state, PAGE)
    for name, _ in flatten_state(state)[0]:
        np.testing.assert_array_equal(img.digests(name), ref.digests(name))
        np.testing.assert_array_equal(
            img.chunk_bytes(name, 0, 4), ref.chunk_bytes(name, 0, 4)
        )


# ------------------------------------------------------- two-phase completion
def test_working_set_event_fires_before_residual(tmp_path, slow_reads):
    state = rng_state(3, scale=8)
    names = [n for n, _ in flatten_state(state)[0]]
    ws = names[:3]
    path = str(tmp_path / "f.jif")
    snapshot(state, path, access_order=names, working_set=ws, page_size=PAGE)

    at_ws = {}
    slow_reads(5e7)
    restorer = SpiceRestorer()
    _, meta, handles, stats = restorer.restore(
        path, wait=False,
        on_working_set=lambda: at_ws.update(complete=stats.complete),
    )
    assert stats.wait_working_set(20)
    assert stats.ws_tensors == 3 and stats.residual_tensors == len(names) - 3
    # at the ws event every ws tensor is resident...
    for n in ws:
        assert handles[n].ready
    # ...and the residual was still streaming when the event fired
    assert at_ws == {"complete": False}
    assert stats.wait_complete(30)
    assert 0 < stats.working_set_s < stats.total_s
    for n in names:
        np.testing.assert_array_equal(
            handles[n].wait(10), np.asarray(dict(flatten_state(state)[0])[n])
        )


def test_residual_demand_boost_still_works(tmp_path, slow_reads):
    """Waiting on a residual tensor after ws completion demand-boosts it
    ahead of the background stream."""
    state = rng_state(4, scale=8)
    names = [n for n, _ in flatten_state(state)[0]]
    path = str(tmp_path / "f.jif")
    snapshot(state, path, access_order=names, working_set=names[:2], page_size=PAGE)
    slow_reads(3e7)
    restorer = SpiceRestorer()
    _, _, handles, stats = restorer.restore(path, wait=False)
    assert stats.wait_working_set(20)
    tail = names[-1]
    got = handles[tail].wait(20)
    np.testing.assert_array_equal(got, np.asarray(dict(flatten_state(state)[0])[tail]))
    assert stats.wait_complete(30)


# ------------------------------------------------------------ pipeline stages
def test_pipeline_stages_compose(tmp_path):
    pipe = SnapshotPipeline(page_size=PAGE)
    state = rng_state(1)
    c, stats = pipe.classify(state)
    order, ws, boundary = pipe.relocate(c, access_order=None)
    assert boundary > 0 and set(order) == set(c.names) and ws == order
    path = str(tmp_path / "staged.jif")
    pipe.write(path, c, order, {"tree": c.treedesc, "access_order": order,
                                "working_set": ws}, None, boundary)
    got, _, _, _ = SpiceRestorer().restore(path)
    assert_state_equal(state, got)


def test_trim_stage_still_applies(tmp_path):
    state = {"params": rng_state(2)["embed"], "opt": {"m": np.ones((4096,), np.float32)}}
    path = str(tmp_path / "f.jif")
    snapshot(state, path, page_size=PAGE, trim_fn=lambda s: {"params": s["params"]})
    got, _, _, _ = SpiceRestorer().restore(path)
    assert "opt" not in got


# ------------------------------------------------------------------ cache O(n)
def test_node_cache_total_bytes_accounting():
    cache = NodeImageCache(capacity_bytes=1 << 30)
    a = BaseImage.from_state("a", {"x": np.ones(4096, np.float32)})
    b = BaseImage.from_state("b", {"x": np.ones(8192, np.float32)})
    cache.put(a)
    assert cache.total_bytes == a.nbytes
    cache.put(b)
    assert cache.total_bytes == a.nbytes + b.nbytes
    # replacing an image must not double-count
    cache.put(BaseImage.from_state("a", {"x": np.ones(2048, np.float32)}))
    assert cache.total_bytes == 2048 * 4 + b.nbytes
    misses = cache.stats["misses"]
    assert cache.get(None) is None
    assert cache.stats["misses"] == misses  # "no base" is not a miss
    # eviction keeps the running total consistent
    cache.capacity = b.nbytes
    cache.put(BaseImage.from_state("c", {"x": np.ones(1024, np.float32)}))
    assert cache.total_bytes == sum(
        img.nbytes for img in cache._images.values()
    )
