"""Program spans of the restore and serving path, read back from a JAX
profiler trace of one cold and one warm invocation of the reduced
qwen1.5-0.5b on the CPU; and the time-to-first-token clock, which ends
with the token on the host."""
import glob
import os
import time

import numpy as np
import pytest
import jax

from repro.configs import get_config
from repro.launch import serve
from repro.serve import instance
from repro.serve.instance import layer_sequence
from repro.serve.invocation import Invocation

PROMPT = np.array([[3, 1, 4, 1, 5, 9, 2, 6]], dtype=np.int32)
SPANS = ("serve.invoke", "serve.generate", "serve.dispatch", "serve.resolve",
         "spice.read", "spice.ring_wait", "spice.upload.issue", "spice.upload.put",
         "spice.upload.land")


@pytest.fixture(scope="module")
def node(tmp_path_factory):
    cfg = get_config("qwen1.5-0.5b").reduced()
    params = serve.model_params(cfg, 0)
    node = serve.serving_node("fused", serve.image_bytes(params), keep_warm=True)
    serve.install_base(node, cfg, params)
    serve.publish_functions(node, str(tmp_path_factory.mktemp("spans")), cfg, params)
    for _ in range(2):  # compile every program: one cold, one warm request
        node.invoke(serve.TUNED_FN, PROMPT, max_new_tokens=2, cfg=cfg)
    yield node, cfg
    node.close()


def _ask(node, cfg, max_new=2):
    h = node.submit_invocation(Invocation(serve.TUNED_FN, PROMPT, max_new, cfg=cfg))
    return h.req, h.result(120)


@pytest.fixture(scope="module")
def ring_jobs():
    """The upload ring's jobs of the traced cold request, fused and full."""
    return {}


@pytest.fixture(scope="module")
def traced(node, tmp_path_factory, ring_jobs):
    """Events of one cold, then one warm request: (name, start_ns, end_ns,
    args) per span of the program, and the two requests' ``req``."""
    node, cfg = node
    assert node.scheduler.drain_residual(60)
    node.evict()
    ring = node.scheduler.upload_stream
    before = ring.snapshot_stats()
    logdir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(logdir)
    try:
        cold_req, cold = _ask(node, cfg)
        # the restore's residual tail lands before the warm request
        assert node.scheduler.drain_residual(60)
        after = ring.snapshot_stats()
        warm_req, warm = _ask(node, cfg)
    finally:
        jax.profiler.stop_trace()
    assert cold.cold and not cold.joined and not warm.cold
    fused = after["fused_patches"] - before["fused_patches"]
    ring_jobs.update(fused=fused, full=after["uploads"] - before["uploads"] - fused)
    path = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)[0]
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in SPANS:
                    events.append((e.name, int(e.start_ns),
                                   int(e.start_ns + e.duration_ns), dict(e.stats)))
    return events, cold_req, warm_req


def _of(events, name, req=None):
    return [ev for ev in events if ev[0] == name and (req is None or ev[3]["req"] == req)]


def _inside(evs, outer):
    return [ev for ev in evs if outer[1] <= ev[1] and ev[2] <= outer[2]]


@pytest.mark.parametrize("name", SPANS)
def test_each_span_is_recorded_with_function_and_req(traced, name):
    events, cold_req, warm_req = traced
    got = _of(events, name)
    assert got, f"no {name} span in the trace"
    for _, _, _, args in got:
        assert args["function"] == serve.TUNED_FN
        assert args["req"] in (cold_req, warm_req)


def test_warm_request_dispatches_each_program_once_inside_generate(node, traced):
    _, cfg = node
    events, _, req = traced
    (gen,) = _of(events, "serve.generate", req)
    dispatch = _of(events, "serve.dispatch", req)
    inside = _inside(dispatch, gen)
    n = len(layer_sequence(cfg))
    assert len(inside) == n + 2
    assert sorted(d[3].get("layer", -1) for d in inside) == [-1, -1, *range(n)]
    # the decode step's programs run after the first token, outside the span
    assert len(dispatch) == 2 * (n + 2)
    assert len(_of(events, "serve.resolve", req)) == 2 + n + n


def test_spans_of_one_request_share_its_req(traced):
    events, cold_req, warm_req = traced
    assert cold_req != warm_req
    roles = {ev[3]["req"]: ev[3]["role"] for ev in _of(events, "serve.invoke")}
    assert roles == {cold_req: "owner", warm_req: "warm"}
    # the restore's reads and uploads run on the reader and the ring's
    # threads, labelled with the invocation that owns the restore
    for name in ("spice.read", "spice.ring_wait", "spice.upload.put", "spice.upload.land"):
        assert _of(events, name) == _of(events, name, cold_req)
    # the lander waits once for every job, put or not
    assert len(_of(events, "spice.upload.land")) >= len(_of(events, "spice.upload.put"))
    # the worker's spans of each request lie inside its serve.invoke
    for req in (cold_req, warm_req):
        (invoke,) = _of(events, "serve.invoke", req)
        for name in ("serve.generate", "serve.dispatch", "serve.resolve"):
            evs = _of(events, name, req)
            assert evs and _inside(evs, invoke) == evs


def test_issue_span_wraps_every_ring_job_and_its_put(traced, ring_jobs):
    events, cold_req, _ = traced
    issues = _of(events, "spice.upload.issue")
    assert issues == _of(events, "spice.upload.issue", cold_req)
    assert all(args["function"] == serve.TUNED_FN for *_, args in issues)
    # one issue per job of the restore, fused and full alike, each landed once
    assert ring_jobs["fused"] > 0 and ring_jobs["full"] > 0
    assert len(issues) == ring_jobs["fused"] + ring_jobs["full"]
    assert len(issues) == len(_of(events, "spice.upload.land"))
    # every put happens inside the issue of its job
    puts = _of(events, "spice.upload.put")
    assert puts and all(any(i[1] <= p[1] and p[2] <= i[2] for i in issues) for p in puts)


class _SlowFetch:
    """A head result whose copy to the host takes ``delay`` seconds."""

    def __init__(self, arr, delay):
        self.arr, self.delay = arr, delay

    def __array__(self, dtype=None, copy=None):
        time.sleep(self.delay)
        return np.asarray(self.arr, dtype)


def test_ttft_ends_with_the_token_on_the_host(node, monkeypatch):
    node, cfg = node
    real = instance._head_fn
    delay = 0.5

    def head_fn(c):
        fn = real(c)
        return lambda *a: _SlowFetch(fn(*a), delay)

    _ask(node, cfg, max_new=1)  # warm
    monkeypatch.setattr(instance, "_head_fn", head_fn)
    t0 = time.perf_counter()
    _, r = _ask(node, cfg, max_new=1)
    wall = time.perf_counter() - t0
    assert not r.cold
    assert delay <= r.ttft_s <= wall
    assert r.tokens.shape == (1, 1)
