"""Warm-state handoff + SLO-driven autoscaler: a handed-off instance is
byte-identical to the source's warm state, a drained node's ledger returns
to pre-restore residency, in-flight work always completes before handoff,
and the control loop grows/shrinks the fleet on sustained signal only."""
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models import lm
from repro.serve.autoscale import AutoScaler, ServiceSLO, SLOMonitor
from repro.serve.cluster import ClusterRouter, FunctionCatalog
from repro.serve.handoff import handoff_warm, wait_idle_warm
from repro.serve.instance import InstanceState
from repro.serve.invocation import QosClass
from repro.serve.node import FixedTTLPolicy, InvokeResult, NodeScheduler

ARCH = "qwen1.5-0.5b"
PROMPT = np.array([[3, 1, 4, 1, 5, 9]], dtype=np.int32)


@pytest.fixture(scope="module")
def catalog_with_zoo(tmp_path_factory):
    d = tmp_path_factory.mktemp("hzoo")
    cfg = get_config(ARCH).reduced()
    catalog = FunctionCatalog()
    for i, fname in enumerate(["hf-a", "hf-b", "hf-c"]):
        params = lm.init_params(cfg, jax.random.PRNGKey(60 + i), jnp.float32)
        catalog.publish(fname, cfg, params, str(d), warm_ttl_s=3600.0,
                        formats=("jif",))
    # compile-cache warmup through a throwaway node
    node = NodeScheduler(registry=catalog.registry)
    node.invoke("hf-a", PROMPT, max_new_tokens=2, cfg=cfg)
    return catalog, cfg, str(d)


def _router(catalog, n=2, **kwargs):
    nodes = [
        NodeScheduler(registry=catalog.registry, keepalive=FixedTTLPolicy(3600.0))
        for _ in range(n)
    ]
    return ClusterRouter(catalog, nodes, **kwargs)


def _leaves(state):
    flat, _ = jax.tree.flatten(state)
    return [np.asarray(a) for a in flat]


def _other(router, name):
    return next(n.name for n in router.nodes if n.name != name)


# ------------------------------------------------------------ the handoff
def test_handoff_byte_identical_and_reroutes(catalog_with_zoo, tmp_path):
    catalog, cfg, _ = catalog_with_zoo
    router = _router(catalog)
    ref = router.invoke("hf-a", PROMPT, max_new_tokens=3, cfg=cfg)
    assert ref.cold
    src_name, dst_name = ref.node, _other(router, ref.node)
    src, dst = router.node(src_name), router.node(dst_name)
    src_leaves = _leaves(src.warm_state("hf-a"))

    hs = handoff_warm(router, "hf-a", src_name, dst_name,
                      handoff_dir=str(tmp_path), cfg=cfg)
    assert hs.ok, hs.reason

    # byte-identity: every leaf of the successor's warm tree equals the
    # source's pre-handoff tree
    dst_leaves = _leaves(dst.warm_state("hf-a"))
    assert len(dst_leaves) == len(src_leaves) > 0
    for a, b in zip(src_leaves, dst_leaves):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(a, b)

    # the move is an infrastructure transfer, not a demand cold start
    assert dst.stats["cold_starts"] == 0
    assert dst.stats["speculative_restores"] == 1
    assert src.instance("hf-a").state is InstanceState.EVICTED
    assert router.replicas("hf-a") == [dst_name]

    # the next request is warm ON THE SUCCESSOR, with identical tokens
    r = router.invoke("hf-a", PROMPT, max_new_tokens=3, cfg=cfg)
    assert not r.cold and r.node == dst_name
    np.testing.assert_array_equal(r.tokens, ref.tokens)
    router.audit()
    router.close()


def test_handoff_delta_is_dirty_state_only(catalog_with_zoo, tmp_path):
    """Warm generation is read-only over the restored tree, so the handoff
    image's private payload is a sliver of the full state."""
    catalog, cfg, _ = catalog_with_zoo
    router = _router(catalog)
    r = router.invoke("hf-b", PROMPT, max_new_tokens=2, cfg=cfg)
    hs = handoff_warm(router, "hf-b", r.node, _other(router, r.node),
                      handoff_dir=str(tmp_path), cfg=cfg)
    assert hs.ok, hs.reason
    assert hs.total_bytes > 0
    assert hs.delta_bytes < 0.1 * hs.total_bytes
    router.audit()
    router.close()


def test_inflight_invocation_completes_before_handoff(catalog_with_zoo, tmp_path,
                                                      slow_reads):
    """A handoff issued while the source instance is busy (here: mid
    restore + generation, its image reads paced) must wait the work out —
    the caller gets a full result, then the handoff lands."""
    catalog, cfg, _ = catalog_with_zoo
    router = _router(catalog)
    seed = router.invoke("hf-c", PROMPT, max_new_tokens=3, cfg=cfg)
    src_name, dst_name = seed.node, _other(router, seed.node)
    src = router.node(src_name)
    src.evict("hf-c")
    # slow restore: instance is busy
    slow_reads(5e7, catalog.registry.get("hf-c").jif_path)
    fut = src.submit("hf-c", PROMPT, max_new_tokens=3, cfg=cfg)
    deadline = time.time() + 10
    while time.time() < deadline:  # wait until the restore is in flight
        inst = src.instance("hf-c")
        if inst is not None and inst.state is InstanceState.RESTORING:
            break
        time.sleep(0.001)
    assert src.instance("hf-c").state is InstanceState.RESTORING
    hs = handoff_warm(router, "hf-c", src_name, dst_name,
                      handoff_dir=str(tmp_path), cfg=cfg)
    r = fut.result(timeout=60)
    assert r.cold  # the in-flight request completed normally...
    np.testing.assert_array_equal(r.tokens, seed.tokens)
    assert hs.ok, hs.reason  # ...and only then did the handoff proceed
    assert router.node(dst_name).instance("hf-c").state is InstanceState.WARM
    router.audit()
    router.close()


def test_handoff_of_missing_instance_fails_gracefully(catalog_with_zoo, tmp_path):
    catalog, cfg, _ = catalog_with_zoo
    router = _router(catalog)
    hs = handoff_warm(router, "hf-a", router.nodes[0].name,
                      router.nodes[1].name, handoff_dir=str(tmp_path),
                      cfg=cfg, timeout=0.2)
    assert not hs.ok and hs.reason
    assert not wait_idle_warm(router.nodes[0], "hf-a", timeout=0.05)
    router.close()


# ------------------------------------------------------------ the drain
def test_drain_returns_ledger_to_prerestore_residency(catalog_with_zoo, tmp_path):
    catalog, cfg, _ = catalog_with_zoo
    router = _router(catalog)
    baseline = {n.name: n.memory.held_bytes() for n in router.nodes}
    r = router.invoke("hf-a", PROMPT, max_new_tokens=2, cfg=cfg)
    src = router.node(r.node)
    assert src.memory.held_bytes() > baseline[r.node]  # warm state resident

    scaler = AutoScaler(router, [], handoff_dir=str(tmp_path), min_nodes=1)
    drained = scaler.drain_node(r.node)
    assert drained is src and src.name not in [n.name for n in router.nodes]
    # every function-state reservation the restore took was returned (the
    # audit ran inside drain_node); what remains charged is only the buffer
    # pool's cached staging — ladder inventory, fully reclaimable to zero
    kinds = src.memory.kind_bytes()
    for kind in ("working_set", "residual", "scratch", "image_cache",
                 "device_image", "chunk_cas"):
        assert kinds.get(kind, 0) == 0, (kind, kinds)
    src.memory.reclaim(1 << 40)
    assert src.memory.held_bytes() == baseline[r.node]
    src.memory.audit()
    # ...and the warm state survived on the successor: next request warm
    r2 = router.invoke("hf-a", PROMPT, max_new_tokens=2, cfg=cfg)
    assert not r2.cold and r2.node != r.node
    np.testing.assert_array_equal(r2.tokens, r.tokens)
    router.audit()
    router.close()


def test_drain_without_handoff_forces_future_cold_start(catalog_with_zoo, tmp_path):
    """The ablation: drain-and-evict throws the warm state away, so the
    next request pays a cold restore — exactly what handoff eliminates."""
    catalog, cfg, _ = catalog_with_zoo
    router = _router(catalog)
    r = router.invoke("hf-b", PROMPT, max_new_tokens=2, cfg=cfg)
    scaler = AutoScaler(router, [], handoff_dir=str(tmp_path), min_nodes=1,
                        handoff=False)
    scaler.drain_node(r.node)
    assert scaler.stats["drain_evictions"] == 1
    assert scaler.stats["handoffs_ok"] == 0
    r2 = router.invoke("hf-b", PROMPT, max_new_tokens=2, cfg=cfg)
    assert r2.cold
    router.audit()
    router.close()


# ----------------------------------------------------------- the monitor
def _result(qos="latency", ttft=0.01, wait=0.0, mode="spice"):
    return InvokeResult(tokens=np.zeros((1, 1), np.int32), cold=False,
                        mode=mode, ttft_s=ttft, queue_wait_s=wait, qos=qos)


def test_slo_monitor_needs_min_samples_to_violate():
    mon = SLOMonitor(window_s=60.0, min_samples=4)
    slos = [ServiceSLO(QosClass.LATENCY, ttft_p99_s=0.1)]
    for _ in range(3):
        mon.observe(_result(ttft=5.0))
    violations, slack = mon.assess(slos)
    assert not violations  # three slow requests are noise, not a trend
    assert not slack       # ...but they do cancel the scale-in signal
    mon.observe(_result(ttft=5.0))
    violations, _ = mon.assess(slos)
    assert violations and "latency:ttft" in violations[0]


def test_slo_monitor_excludes_prewarms_and_reports_slack():
    mon = SLOMonitor(window_s=60.0, min_samples=2)
    slos = [ServiceSLO(QosClass.LATENCY, ttft_p99_s=0.1,
                       queue_wait_p95_s=0.1)]
    for _ in range(8):
        mon.observe(_result(ttft=0.01, wait=0.01))
        mon.observe(_result(ttft=99.0, mode="prewarm"))  # infrastructure
    violations, slack = mon.assess(slos)
    assert not violations and slack
    assert mon.percentile(QosClass.LATENCY, "ttft", 0.99) == \
        pytest.approx(0.01)
    # an idle class (no samples) counts as slack, not as a violation
    violations, slack = mon.assess([ServiceSLO(QosClass.BATCH, ttft_p99_s=0.1)])
    assert not violations and slack


# ------------------------------------------------------- the control loop
def test_autoscaler_scales_out_on_sustained_violation(catalog_with_zoo, tmp_path):
    catalog, cfg, _ = catalog_with_zoo
    router = _router(catalog, n=1)
    mon = SLOMonitor(window_s=60.0, min_samples=2)
    scaler = AutoScaler(
        router, [ServiceSLO(QosClass.LATENCY, ttft_p99_s=0.05)],
        handoff_dir=str(tmp_path), monitor=mon, scale_out_after=2,
        max_nodes=2,
        node_factory=lambda name: NodeScheduler(
            registry=catalog.registry, keepalive=FixedTTLPolicy(3600.0),
            name=name),
    )
    for _ in range(4):
        mon.observe(_result(ttft=1.0))
    assert scaler.tick() is None  # hysteresis: one violating tick buys nothing
    assert scaler.tick() == "scale_out"
    assert len(router.nodes) == 2 and scaler.stats["scale_outs"] == 1
    assert scaler.tick() is None  # max_nodes caps further growth
    # the grown node serves traffic (registry adopted, monitor wired)
    grown = router.nodes[-1]
    r = grown.invoke("hf-c", PROMPT, max_new_tokens=2, cfg=cfg)
    assert r.cold and grown.on_result == mon.observe
    router.audit()
    router.close()


def test_autoscaler_scales_in_on_sustained_slack(catalog_with_zoo, tmp_path):
    catalog, cfg, _ = catalog_with_zoo
    router = _router(catalog, n=3)
    r = router.invoke("hf-a", PROMPT, max_new_tokens=2, cfg=cfg)
    scaler = AutoScaler(
        router, [ServiceSLO(QosClass.LATENCY, ttft_p99_s=0.5)],
        handoff_dir=str(tmp_path), min_nodes=2, scale_in_after=2,
    )
    assert scaler.tick() is None  # idle window = slack, but hysteresis holds
    assert scaler.tick() == "scale_in"
    # least-loaded victim: an EMPTY node went first — the warm instance
    # was never touched, no handoff was needed
    assert len(router.nodes) == 2 and scaler.stats["handoffs_ok"] == 0
    assert any(n.name == r.node for n in router.nodes)
    for _ in range(4):
        assert scaler.tick() != "scale_in"  # min_nodes floors the fleet
    assert len(router.nodes) == 2
    assert scaler.node_seconds() > 0
    router.audit()
    router.close()


# ------------------------------------------------------ load-probe cache
def test_load_probe_cache_invalidated_by_lifecycle_edge(catalog_with_zoo):
    catalog, cfg, _ = catalog_with_zoo
    node = NodeScheduler(registry=catalog.registry,
                         keepalive=FixedTTLPolicy(3600.0), load_ttl_s=30.0)
    l1 = node.load()
    assert node.load() is l1  # within TTL, no transitions: cached snapshot
    node.invoke("hf-a", PROMPT, max_new_tokens=2, cfg=cfg)
    l2 = node.load()  # lifecycle edges bumped the epoch despite the TTL
    assert l2 is not l1 and "hf-a" in l2.warm
    node.evict("hf-a")
    assert "hf-a" not in node.load().warm
    node.memory.audit()
    node.close()
