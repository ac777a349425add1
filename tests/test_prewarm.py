"""Warmth policy engine: arrival histograms, adaptive keep-alive TTLs,
cost-aware eviction ranking, and speculative BATCH-class pre-warms that
join cleanly with real traffic and yield to the reclaim ladder."""
import time
import types

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models import lm
from repro.serve.cluster import ClusterRouter, FunctionCatalog
from repro.serve.instance import InstanceState
from repro.serve.invocation import EVT_RESTORING, Invocation, QosClass
from repro.serve.node import KeepAlivePolicy, NodeScheduler
from repro.serve.prewarm import ArrivalTracker, PrewarmEngine, PrewarmPolicy

ARCH = "qwen1.5-0.5b"
PROMPT = np.array([[2, 7, 1, 8, 2, 8]], dtype=np.int32)


@pytest.fixture(scope="module")
def catalog_with_fns(tmp_path_factory):
    d = tmp_path_factory.mktemp("pwzoo")
    cfg = get_config(ARCH).reduced()
    catalog = FunctionCatalog()
    for i, fname in enumerate(["pw-a", "pw-b", "pw-c"]):
        params = lm.init_params(cfg, jax.random.PRNGKey(80 + i), jnp.float32)
        catalog.publish(fname, cfg, params, str(d), warm_ttl_s=0.0,
                        formats=("jif",))
    node = NodeScheduler(registry=catalog.registry)  # compile-cache warmup
    node.invoke("pw-a", PROMPT, max_new_tokens=2, cfg=cfg)
    return catalog, cfg


# ------------------------------------------------------------ ArrivalTracker
def test_tracker_needs_two_arrivals_for_a_gap():
    tr = ArrivalTracker()
    tr.record("f", now=100.0)
    assert tr.observations("f") == 0
    assert tr.gap_quantile("f", 0.5) is None
    assert tr.predict_eta("f", now=101.0) is None
    assert tr.observations("missing") == 0


def test_tracker_quantiles_and_eta_for_periodic_traffic():
    tr = ArrivalTracker()
    for t in (0.0, 0.4, 0.8, 1.2):
        tr.record("f", now=t)
    assert tr.observations("f") == 3
    # all gaps land in one bucket whose max is the exact period
    assert tr.gap_quantile("f", 0.5) == pytest.approx(0.4)
    assert tr.gap_quantile("f", 0.9) == pytest.approx(0.4)
    # predicted next arrival = last + median gap
    assert tr.predict_eta("f", now=1.3) == pytest.approx(0.3)
    assert tr.predict_eta("f", now=2.0) == pytest.approx(-0.4)  # overdue


def test_tracker_quantiles_are_monotonic_with_mixed_gaps():
    tr = ArrivalTracker()
    t = 0.0
    tr.record("f", now=t)
    for gap in (0.1, 0.1, 0.1, 0.1, 0.1, 0.1, 0.1, 3.0):
        t += gap
        tr.record("f", now=t)
    q50, q95 = tr.gap_quantile("f", 0.5), tr.gap_quantile("f", 0.95)
    assert q50 <= q95
    assert q50 == pytest.approx(0.1)
    assert q95 == pytest.approx(3.0)
    assert tr.observations("f") == 8
    assert "f" in tr.snapshot()


def test_tracker_min_observations_gate():
    tr = ArrivalTracker()
    for t in (0.0, 0.5):
        tr.record("f", now=t)
    assert tr.gap_quantile("f", 0.5, min_observations=2) is None
    assert tr.predict_eta("f", now=0.6, min_observations=2) is None
    tr.record("f", now=1.0)
    assert tr.gap_quantile("f", 0.5, min_observations=2) == pytest.approx(0.5)


# ----------------------------------------------------------- adaptive TTLs
def _spec(name, warm_ttl_s=7.0):
    return types.SimpleNamespace(name=name, warm_ttl_s=warm_ttl_s)


def test_ttl_for_head_tail_and_fallback():
    tr = ArrivalTracker()
    for t in (0.0, 0.2, 0.4, 0.6):  # head: periodic, short gaps
        tr.record("head", now=t)
    for t in (0.0, 100.0, 200.0, 300.0):  # long tail: huge gaps
        tr.record("tail", now=t)
    pol = PrewarmPolicy(tr, max_ttl_s=30.0, tail_ttl_s=0.5, ttl_margin=1.25,
                        min_observations=2)
    # head window = p90 gap x margin, above the floor
    assert pol.ttl_for(_spec("head")) == pytest.approx(0.25)
    # tail would need a 125 s window: rely on restore instead
    assert pol.ttl_for(_spec("tail")) == 0.5
    # no history: the spec's static TTL ...
    assert pol.ttl_for(_spec("unknown")) == 7.0
    # ... unless an explicit default overrides it
    pol2 = PrewarmPolicy(tr, default_ttl_s=1.5, min_observations=2)
    assert pol2.ttl_for(_spec("unknown")) == 1.5


# ------------------------------------------------------- eviction contracts
class _Inst:
    def __init__(self, name, last_used, nbytes):
        self.spec = types.SimpleNamespace(name=name)
        self.last_used = last_used
        self.restore_stats = None
        self.memory_bytes = nbytes


def test_default_victims_honors_need_evict_lru_first():
    """Regression: the default policy used to return the whole warm list
    regardless of ``need_evict``."""
    pol = KeepAlivePolicy()
    warm = [_Inst("a", 3.0, 1), _Inst("b", 1.0, 1), _Inst("c", 2.0, 1)]
    got = pol.victims(warm, need_evict=2)
    assert [i.spec.name for i in got] == ["b", "c"]  # LRU-first, at most 2
    assert pol.victims(warm, need_evict=0) == []
    assert len(pol.victims(warm, need_evict=99)) == 3


def test_cost_aware_victims_rank_cheap_and_far_first():
    now = time.monotonic()
    tr = ArrivalTracker()
    # "soon": period 1.0, next arrival ~now -> tiny eta -> penalty spike
    tr.record("soon", now=now - 2.0)
    tr.record("soon", now=now - 1.0)
    # "later": period 30, next arrival ~now+15
    tr.record("later", now=now - 45.0)
    tr.record("later", now=now - 15.0)
    pol = PrewarmPolicy(tr, min_observations=1, unknown_eta_s=60.0,
                        cost_fn=lambda i: i.memory_bytes)
    soon = _Inst("soon", 5.0, 1 << 20)
    later = _Inst("later", 1.0, 1 << 20)
    pricey = _Inst("pricey-later", 2.0, 64 << 20)  # no history: eta=60 s
    got = pol.victims([soon, later, pricey], need_evict=2)
    # cheapest-to-re-restore x farthest-from-needed go first; the
    # imminent instance survives even though it is equally cheap
    assert [i.spec.name for i in got] == ["later", "pricey-later"]
    assert pol.victims([soon, later, pricey], need_evict=0) == []


# ------------------------------------------------- speculation end-to-end
def _warm_history(engine, fname, period=0.2, n=3):
    """Feed ``n`` arrivals ending now, so the predicted next arrival is
    ``period`` seconds out (inside any reasonable horizon)."""
    now = time.monotonic()
    for k in range(n, 0, -1):
        engine.on_arrival(fname, now=now - period * (k - 1))


def test_speculative_restore_promotes_warm_without_generation(catalog_with_fns):
    catalog, cfg = catalog_with_fns
    tracker = ArrivalTracker()
    engine = PrewarmEngine(tracker, horizon_s=5.0, interval_s=None,
                           min_observations=2)
    node = NodeScheduler(
        registry=catalog.registry,
        keepalive=PrewarmPolicy(tracker, default_ttl_s=30.0,
                                min_observations=2),
    )
    router = ClusterRouter(catalog, [node], prewarm=engine)
    try:
        # one real invocation: sticky placement + the instance's cfg
        r0 = router.invoke("pw-a", PROMPT, max_new_tokens=2, cfg=cfg)
        assert r0.cold
        node.evict("pw-a")
        _warm_history(engine, "pw-a")
        assert engine.tick() == 1
        assert engine.drain(30.0)
        inst = node.instance("pw-a")
        assert inst.state is InstanceState.WARM
        assert node.stats["speculative_restores"] == 1
        assert node.stats["cold_starts"] == 1  # only the priming call
        assert engine.stats["speculative_ok"] == 1
        # the real arrival the engine predicted: a plain warm hit
        r1 = router.invoke("pw-a", PROMPT, max_new_tokens=2, cfg=cfg)
        assert not r1.cold
        np.testing.assert_array_equal(r0.tokens, r1.tokens)
    finally:
        router.close()


def test_engine_suppresses_resident_and_unknown_functions(catalog_with_fns):
    catalog, cfg = catalog_with_fns
    engine = PrewarmEngine(horizon_s=5.0, interval_s=None, min_observations=2)
    node = NodeScheduler(
        registry=catalog.registry,
        keepalive=PrewarmPolicy(engine.tracker, default_ttl_s=30.0,
                                min_observations=2),
    )
    router = ClusterRouter(catalog, [node], prewarm=engine)
    try:
        router.invoke("pw-a", PROMPT, max_new_tokens=2, cfg=cfg)
        _warm_history(engine, "pw-a")      # warm: must not re-restore
        _warm_history(engine, "ghost-fn")  # tracked but never published
        assert engine.tick() == 0
        assert engine.stats["suppressed_resident"] == 1
        assert node.stats["speculative_restores"] == 0
    finally:
        router.close()


def test_real_invocation_joins_inflight_speculative_restore(catalog_with_fns,
                                                           slow_reads):
    """A real arrival mid-speculation rides the SAME restore: exactly one
    restore owner (the speculation), the real result marked joined, its
    timeline showing the RESTORING ride."""
    catalog, cfg = catalog_with_fns
    engine = PrewarmEngine(horizon_s=5.0, interval_s=None, min_observations=2)
    node = NodeScheduler(
        registry=catalog.registry,
        keepalive=PrewarmPolicy(engine.tracker, default_ttl_s=30.0,
                                min_observations=2),
    )
    router = ClusterRouter(catalog, [node], prewarm=engine)
    try:
        router.invoke("pw-b", PROMPT, max_new_tokens=2, cfg=cfg)
        node.evict("pw-b")
        # slow restore: ~1 s window
        slow_reads(4e6, catalog.registry.get("pw-b").jif_path)
        _warm_history(engine, "pw-b")
        assert engine.tick() == 1
        inst = node.instance("pw-b")
        deadline = time.monotonic() + 10.0
        while (inst.state is not InstanceState.RESTORING
               and time.monotonic() < deadline):
            time.sleep(0.002)
        assert inst.state is InstanceState.RESTORING
        h = router.submit_invocation(Invocation(
            function="pw-b", prompt=PROMPT, max_new_tokens=2,
            cfg=cfg, qos=QosClass.LATENCY,
        ))
        r = h.result(60.0)
        assert r.joined and r.cold
        assert h.event_ts(EVT_RESTORING) is not None
        assert engine.drain(30.0)
        # one restore total for this round: the speculation owned it
        assert node.stats["speculative_restores"] == 1
        assert node.stats["cold_starts"] == 1  # only the priming call
    finally:
        router.close()


def test_redundant_speculation_against_warm_instance_is_a_noop(catalog_with_fns):
    catalog, cfg = catalog_with_fns
    node = NodeScheduler(
        registry=catalog.registry,
        keepalive=PrewarmPolicy(ArrivalTracker(), default_ttl_s=30.0),
    )
    router = ClusterRouter(catalog, [node])
    try:
        router.invoke("pw-a", PROMPT, max_new_tokens=2, cfg=cfg)
        h = router.submit_invocation(Invocation(
            function="pw-a", prompt=None, max_new_tokens=0,
            qos=QosClass.BATCH, prewarm=True,
        ))
        r = h.result(30.0)
        assert r.mode == "prewarm" and not r.cold
        assert node.stats["prewarm_redundant"] == 1
        assert node.stats["speculative_restores"] == 0
    finally:
        router.close()


# ------------------------------------------------------- reaper + reclaim
def test_reaper_honors_adaptive_ttls(catalog_with_fns):
    catalog, cfg = catalog_with_fns
    tracker = ArrivalTracker()
    node = NodeScheduler(
        registry=catalog.registry,
        keepalive=PrewarmPolicy(tracker, min_observations=1, max_ttl_s=30.0),
    )
    router = ClusterRouter(catalog, [node])
    try:
        now = time.monotonic()
        for t in (now - 0.3, now - 0.15, now):   # pw-b: ~0.19 s window
            tracker.record("pw-b", now=t)
        for t in (now - 20.0, now - 10.0, now):  # pw-c: ~12.5 s window
            tracker.record("pw-c", now=t)
        router.invoke("pw-b", PROMPT, max_new_tokens=2, cfg=cfg)
        router.invoke("pw-c", PROMPT, max_new_tokens=2, cfg=cfg)
        assert node.instance("pw-b").state is InstanceState.WARM
        time.sleep(0.4)  # past pw-b's adaptive TTL, well inside pw-c's
        assert node.reap_expired() == 1
        assert node.instance("pw-b").state is InstanceState.EVICTED
        assert node.instance("pw-c").state is InstanceState.WARM
    finally:
        router.close()


def test_mispredicted_speculation_yields_to_reclaim_ladder(catalog_with_fns):
    """A speculative instance whose predicted arrival never comes is just
    idle warm memory: the reclaim ladder takes it back and the ledger
    stays audit-clean."""
    catalog, cfg = catalog_with_fns
    engine = PrewarmEngine(horizon_s=5.0, interval_s=None, min_observations=2)
    node = NodeScheduler(
        registry=catalog.registry,
        keepalive=PrewarmPolicy(engine.tracker, default_ttl_s=300.0,
                                min_observations=2),
    )
    router = ClusterRouter(catalog, [node], prewarm=engine)
    try:
        router.invoke("pw-c", PROMPT, max_new_tokens=2, cfg=cfg)
        node.evict("pw-c")
        _warm_history(engine, "pw-c")
        assert engine.tick() == 1
        assert engine.drain(30.0)
        inst = node.instance("pw-c")
        assert inst.state is InstanceState.WARM
        freed = node.memory.reclaim(node.memory.held_bytes() + 1)
        assert freed > 0
        assert inst.state is InstanceState.EVICTED
        assert node.stats["lru_evictions"] >= 1
        node.memory.audit()  # raises if the ledger disagrees
    finally:
        router.close()
