"""Train→serve deployment pipeline: versioned publishes share base chunks
through the CAS, the canary A/B split is deterministic under seed,
promote/rollback serve byte-identical state, retired-version GC leaves the
CAS audit clean, and colocated BATCH training never starves LATENCY work."""
import threading
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import ChunkStore, SpiceRestorer
from repro.ft.manager import CheckpointManager
from repro.ft.publish import DeltaPublishCallback
from repro.launch.serve import finetune
from repro.serve.cluster import ClusterRouter, FunctionCatalog
from repro.serve.deploy import (
    ColocatedTrainer,
    RolloutController,
    TokenHealthGate,
)
from repro.serve.instance import layerwise_state
from repro.serve.invocation import AdmissionController, Invocation, Overloaded, QosClass
from repro.serve.node import FixedTTLPolicy, NodeScheduler
from repro.models import lm

ARCH = "qwen1.5-0.5b"
PROMPT = np.array([[3, 1, 4, 1, 5, 9]], dtype=np.int32)


@pytest.fixture(scope="module")
def deployed(tmp_path_factory):
    """One catalog + CAS with three published base functions and their
    init params; a throwaway node warms the compile cache."""
    d = tmp_path_factory.mktemp("deploy")
    cfg = get_config(ARCH).reduced()
    store = ChunkStore(str(d / "cas"))
    catalog = FunctionCatalog(chunk_store=store)
    zoo = {}
    # one base per test that grows a lineage: versions register under
    # "<fname>@vN", so lineages sharing a name would collide across tests
    for i, fname in enumerate(["dp-a", "dp-b", "dp-c", "dp-d", "dp-e", "dp-f"]):
        params = lm.init_params(cfg, jax.random.PRNGKey(80 + i), jnp.float32)
        catalog.publish(fname, cfg, params, str(d), warm_ttl_s=3600.0,
                        formats=("jif",))
        zoo[fname] = params
    node = NodeScheduler(registry=catalog.registry)
    node.invoke("dp-a", PROMPT, max_new_tokens=2, cfg=cfg)
    return catalog, cfg, str(d), zoo, store


def _router(catalog, n=2):
    nodes = [
        NodeScheduler(registry=catalog.registry, keepalive=FixedTTLPolicy(3600.0))
        for _ in range(n)
    ]
    return ClusterRouter(catalog, nodes)


def _leaves(state):
    flat, _ = jax.tree.flatten(state)
    return [np.asarray(a) for a in flat]


# -------------------------------------------------- CAS chunk sharing
def test_versioned_publish_shares_base_chunks(deployed, tmp_path):
    catalog, cfg, d, zoo, store = deployed
    deploy = RolloutController(catalog, seed=7, dirpath=str(tmp_path))
    deploy.track("dp-a")

    before = store.audit()  # also asserts the invariant pre-publish
    rec = deploy.publish_version(
        "dp-a", cfg, finetune(cfg, zoo["dp-a"], 0.01), step=1
    )
    after = store.audit()

    # the delta pays only for the dirtied fraction, not a second full image
    assert 0 < rec.private_bytes < 0.6 * rec.total_bytes
    v1 = deploy.current("dp-a")
    assert rec.private_bytes < 0.6 * v1.total_bytes
    # CAS growth is the delta's chunks only: far fewer than a full image's
    new_chunks = after["chunks"] - before["chunks"]
    assert 0 < new_chunks
    # the version is a real registered function restorable on any node
    assert catalog.registry.get(rec.name).jif_path == rec.jif_path
    state, _, _, _ = SpiceRestorer().restore(rec.jif_path)
    ref = layerwise_state(cfg, finetune(cfg, zoo["dp-a"], 0.01))
    for a, b in zip(_leaves(ref), _leaves(state)):
        np.testing.assert_array_equal(a, b)


# -------------------------------------------- deterministic canary split
def test_canary_fraction_deterministic_under_seed(deployed, tmp_path):
    catalog, cfg, d, zoo, store = deployed
    deploy = RolloutController(catalog, seed=123, dirpath=str(tmp_path))
    deploy.track("dp-b")
    rec = deploy.publish_version("dp-b", cfg, finetune(cfg, zoo["dp-b"], 0.02))

    deploy.begin_canary("dp-b", rec.version, fraction=0.3)
    seq1 = [deploy.resolve("dp-b") for _ in range(400)]
    # re-arming the same (seed, version, name) canary replays the exact
    # same routing decisions — the split is a pure function of the seed
    deploy.begin_canary("dp-b", rec.version, fraction=0.3)
    seq2 = [deploy.resolve("dp-b") for _ in range(400)]
    assert seq1 == seq2

    frac = sum(s == rec.name for s in seq1) / len(seq1)
    assert 0.2 < frac < 0.4  # the requested fraction, not all-or-nothing
    assert {s for s in seq1} == {"dp-b", rec.name}

    # a different controller seed routes differently
    other = RolloutController(catalog, seed=124, dirpath=str(tmp_path))
    other.track("dp-b")
    other.lineage("dp-b").records[rec.version] = rec
    other.begin_canary("dp-b", rec.version, fraction=0.3)
    assert [other.resolve("dp-b") for _ in range(400)] != seq1

    # names that are not logical lineages pass through untouched
    assert deploy.resolve(rec.name) == rec.name
    assert deploy.resolve("unknown-fn") == "unknown-fn"
    deploy.rollback("dp-b")  # reject the canary; dp-b lineage back to v1


# ------------------------------------- promote / rollback byte-identity
def test_promote_rollback_byte_identity(deployed, tmp_path):
    catalog, cfg, d, zoo, store = deployed
    deploy = RolloutController(catalog, seed=5, dirpath=str(tmp_path))
    deploy.track("dp-c")
    tuned = finetune(cfg, zoo["dp-c"], 0.03)
    rec = deploy.publish_version("dp-c", cfg, tuned, step=2)
    deploy.begin_canary("dp-c", rec.version, fraction=0.5)

    publishes_before = catalog.stats["publishes"]
    deploy.promote("dp-c")
    assert deploy.current("dp-c").version == rec.version
    assert deploy.canary("dp-c") is None
    assert deploy.resolve("dp-c") == rec.name  # all traffic on v2 now
    state, _, _, _ = SpiceRestorer().restore(deploy.current("dp-c").jif_path)
    for a, b in zip(_leaves(layerwise_state(cfg, tuned)), _leaves(state)):
        np.testing.assert_array_equal(a, b)

    # instant rollback: pointer repoint to the parent, zero new publishes,
    # and a fresh restore of what now serves is leaf-by-leaf identical to
    # the original base state
    back = deploy.rollback("dp-c")
    assert back.version == 1 and deploy.resolve("dp-c") == "dp-c"
    assert catalog.stats["publishes"] == publishes_before
    state, _, _, _ = SpiceRestorer().restore(back.jif_path)
    ref = layerwise_state(cfg, zoo["dp-c"])
    for a, b in zip(_leaves(ref), _leaves(state)):
        np.testing.assert_array_equal(a, b)
    store.audit()


# --------------------------------------------------- retired-version GC
def test_retired_version_gc_leaves_cas_clean(deployed, tmp_path):
    catalog, cfg, d, zoo, store = deployed
    deploy = RolloutController(catalog, seed=9, dirpath=str(tmp_path))
    deploy.track("dp-d")
    before = store.audit()
    rec = deploy.publish_version("dp-d", cfg, finetune(cfg, zoo["dp-d"], 0.04))
    deploy.begin_canary("dp-d", rec.version, fraction=0.25)
    deploy.rollback("dp-d")  # gate failed: reject the canary

    # still registered until GC actually retires it
    assert rec.name in catalog.registry
    retired = deploy.gc_retired("dp-d")
    assert retired == [rec.name]
    assert rec.name not in catalog.registry
    import os
    assert not os.path.exists(rec.jif_path)
    # every chunk the dead version uniquely owned is unlinked; the store
    # invariant (disk == refs) holds and the base's chunks survive
    after = store.audit()
    assert after["chunks"] == before["chunks"]
    assert after["refs"] == before["refs"]

    # the stable ancestor of the live head is NOT collectable
    with pytest.raises(ValueError):
        deploy.retire("dp-d", 1)


# ---------------------------------------- quality gate end-to-end rollout
def test_canary_gate_promotes_over_router(deployed, tmp_path):
    catalog, cfg, d, zoo, store = deployed
    router = _router(catalog)
    deploy = RolloutController(catalog, seed=11, dirpath=str(tmp_path)).attach(router)
    deploy.track("dp-e")
    rec = deploy.publish_version("dp-e", cfg, finetune(cfg, zoo["dp-e"], 0.05))
    deploy.begin_canary("dp-e", rec.version, fraction=0.5)

    # the router resolves the logical name through the controller
    results = [
        router.invoke("dp-e", PROMPT, max_new_tokens=2, cfg=cfg)
        for _ in range(8)
    ]
    served = {r.function for r in results}
    assert served == {"dp-e", rec.name}  # both versions took traffic

    ok = deploy.evaluate_canary(
        "dp-e", PROMPT, gate=TokenHealthGate(vocab_size=cfg.vocab_size),
        n_probes=2, max_new_tokens=2, cfg=cfg,
    )
    assert ok and deploy.current("dp-e").version == rec.version

    # a failing gate rejects and keeps the lineage where it was
    rec3 = deploy.publish_version("dp-e", cfg, finetune(cfg, zoo["dp-e"], 0.06))
    deploy.begin_canary("dp-e", rec3.version, fraction=0.5)

    class AlwaysBad:
        def evaluate(self, results):
            return False

    ok = deploy.evaluate_canary("dp-e", PROMPT, gate=AlwaysBad(),
                                n_probes=1, max_new_tokens=2, cfg=cfg)
    assert not ok
    assert deploy.current("dp-e").version == rec.version
    assert deploy.canary("dp-e") is None
    router.audit()
    router.close()


# ------------------------------------------- serve/train colocation QoS
def test_colocated_batch_training_never_starves_latency(deployed):
    catalog, cfg, d, zoo, store = deployed
    node = NodeScheduler(
        registry=catalog.registry,
        keepalive=FixedTTLPolicy(3600.0),
        max_workers=2,
        admission=AdmissionController(max_batch_inflight=1),
    )
    # warm the serving function first
    r = node.invoke("dp-c", PROMPT, max_new_tokens=2, cfg=cfg)
    assert r.cold

    # a second concurrent BATCH payload is REFUSED: the in-flight cap keeps
    # background compute from occupying every worker
    blocker = node.submit_invocation(Invocation(
        function="train:ft", qos=QosClass.BATCH,
        payload=lambda: time.sleep(0.3),
    ))
    with pytest.raises(Overloaded):
        node.submit_invocation(Invocation(
            function="train:ft", qos=QosClass.BATCH,
            payload=lambda: time.sleep(0.3),
        ))

    # a training loop grinding BATCH steps leaves LATENCY service intact
    trainer = ColocatedTrainer(node, job_name="ft")
    stop = threading.Event()

    def grind():
        while not stop.is_set():
            trainer.step(time.sleep, 0.05)

    t = threading.Thread(target=grind, daemon=True)
    t.start()
    try:
        for _ in range(5):
            lr = node.submit_invocation(Invocation(
                function="dp-c", prompt=PROMPT, max_new_tokens=2,
                cfg=cfg, qos=QosClass.LATENCY,
            )).result(10.0)
            assert not lr.cold          # stayed warm throughout
            assert lr.queue_wait_s < 0.25  # never parked behind training
    finally:
        stop.set()
        t.join(5.0)
    blocker.result(10.0)
    assert node.stats["payload_runs"] >= 2
    assert trainer.stats["steps"] >= 1
    node.memory.audit()
    node.close()


# ----------------------------------- checkpoint callback -> new versions
def test_checkpoint_callback_publishes_versions(deployed, tmp_path):
    catalog, cfg, d, zoo, store = deployed
    deploy = RolloutController(catalog, seed=3, dirpath=str(tmp_path / "pub"))
    cb = DeltaPublishCallback(
        deploy, "dp-f", cfg, every=2, canary_fraction=0.5,
        extract=lambda s: s["params"],
    )
    mgr = CheckpointManager(str(tmp_path / "ckpt"), async_save=False,
                            callbacks=[cb])
    for step in range(4):  # 4 saves, every=2 -> 2 published versions
        state = {"params": finetune(cfg, zoo["dp-f"], 0.001 * (step + 1)),
                 "opt": {"count": np.int32(step)}}
        mgr.save(step, state, blocking=True)
    assert [r.step for r in cb.published] == [0, 2]
    assert len(deploy.versions("dp-f")) == 3  # v1 + the two publishes
    # latest publish is the canary (auto_canary), superseding the first
    assert deploy.canary("dp-f").version == cb.published[-1].version
    assert cb.published[0].status == "rejected"
    deploy.rollback("dp-f")
    assert deploy.gc_retired("dp-f") != []
    store.audit()
