"""Serverless serving engine — compatibility facade.

The monolithic ``ServerlessNode`` was split into a layered runtime:

* :mod:`repro.core.iosched`   — node-wide prefetch I/O scheduler (per-stream
  queues, demand boost, bandwidth arbitration),
* :mod:`repro.serve.instance` — per-function lifecycle state machines
  (COLD → RESTORING → WARM → EVICTED) + layer-gated generation,
* :mod:`repro.serve.node`     — the per-node DATA PLANE: concurrent
  admission, keep-alive TTL, LRU eviction under a shared memory budget,
* :mod:`repro.serve.cluster`  — the CONTROL PLANE (`FunctionCatalog`:
  publish/relayout/registry ownership) and the N-node `ClusterRouter`
  with pluggable snapshot-locality-aware placement.

``ServerlessNode`` here is a thin facade composing a catalog with a
one-node router, so the existing examples and tests keep
their `publish`/`invoke`/`evict` surface; new code should target the
layers directly.
"""
from __future__ import annotations

from typing import Optional

from repro.core import BufferPool, FunctionRegistry, NodeImageCache, PrefetchIOScheduler
from repro.serve.cluster import (  # re-exported: the cluster layer
    ClusterRouter,
    FunctionCatalog,
    LeastLoaded,
    LocalityFirst,
    PlacementPolicy,
    RoundRobin,
)
from repro.serve.instance import (  # re-exported: public serving helpers
    FunctionInstance,
    InstanceState,
    generate,
    layer_sequence,
    layerwise_state,
    wait_tree,
)
from repro.serve.instance import wait_tree as _wait_tree  # legacy alias
from repro.serve.invocation import (  # re-exported: the typed request surface
    AdmissionController,
    DeadlineExceeded,
    Invocation,
    InvocationCancelled,
    InvocationError,
    InvocationHandle,
    Overloaded,
    QosClass,
    deadline_in,
)
from repro.serve.node import (
    FixedTTLPolicy,
    InvokeResult,
    KeepAlivePolicy,
    NodeLoad,
    NodeScheduler,
    NoKeepAlive,
)
from repro.serve.prewarm import (  # re-exported: the warmth policy engine
    ArrivalTracker,
    PrewarmEngine,
    PrewarmPolicy,
)
from repro.serve.deploy import (  # re-exported: the deployment pipeline
    ColocatedTrainer,
    QualityGate,
    RolloutController,
    TokenHealthGate,
    VersionedFunction,
    VersionRecord,
)

__all__ = [
    "ServerlessNode",
    "NodeScheduler",
    "NodeLoad",
    "InvokeResult",
    "Invocation",
    "InvocationHandle",
    "QosClass",
    "AdmissionController",
    "InvocationError",
    "Overloaded",
    "DeadlineExceeded",
    "InvocationCancelled",
    "deadline_in",
    "KeepAlivePolicy",
    "FixedTTLPolicy",
    "NoKeepAlive",
    "ArrivalTracker",
    "PrewarmPolicy",
    "PrewarmEngine",
    "FunctionCatalog",
    "ClusterRouter",
    "PlacementPolicy",
    "LocalityFirst",
    "RoundRobin",
    "LeastLoaded",
    "FunctionInstance",
    "InstanceState",
    "layer_sequence",
    "layerwise_state",
    "generate",
    "wait_tree",
    "RolloutController",
    "VersionedFunction",
    "VersionRecord",
    "QualityGate",
    "TokenHealthGate",
    "ColocatedTrainer",
]


class ServerlessNode:
    """One node: catalog (control plane) + a single-node router over one
    `NodeScheduler` (data plane).

    Thin facade; construction signature and the ``publish`` / ``invoke`` /
    ``evict`` surface match the seed engine.  The catalog's authoring
    base-image cache IS the node's serving cache here (one machine), so
    ``node_cache.put(...)`` keeps feeding both publish-time dedup and
    restore-time base resolution."""

    def __init__(
        self,
        registry: Optional[FunctionRegistry] = None,
        node_cache: Optional[NodeImageCache] = None,
        pool: Optional[BufferPool] = None,
        scheduler: Optional[NodeScheduler] = None,
        catalog: Optional[FunctionCatalog] = None,
        prewarm: Optional[PrewarmEngine] = None,
        **scheduler_kwargs,
    ):
        if scheduler is None and catalog is not None and node_cache is None:
            # injected catalog, default scheduler: share the catalog's
            # authoring cache as the serving cache too, so base_name-
            # published functions restore (their base lives there)
            node_cache = catalog.base_images
        self._sched = scheduler or NodeScheduler(
            registry=registry, node_cache=node_cache, pool=pool,
            **scheduler_kwargs,
        )
        self._catalog = catalog or FunctionCatalog(
            registry=self._sched.registry, base_images=self._sched.node_cache
        )
        self._router = ClusterRouter(
            self._catalog, [self._sched], prewarm=prewarm
        )

    # shared-component accessors
    @property
    def scheduler(self) -> NodeScheduler:
        return self._sched

    @property
    def catalog(self) -> FunctionCatalog:
        return self._catalog

    @property
    def router(self) -> ClusterRouter:
        return self._router

    @property
    def registry(self) -> FunctionRegistry:
        return self._catalog.registry

    @property
    def node_cache(self) -> NodeImageCache:
        return self._sched.node_cache

    @property
    def iosched(self) -> PrefetchIOScheduler:
        return self._sched.iosched

    @property
    def memory(self):
        """The node's memory ledger (:class:`NodeMemoryManager`)."""
        return self._sched.memory

    @property
    def pool(self) -> BufferPool:
        return self._sched.pool

    def publish(self, *args, **kwargs):
        # the writer's state copy is node memory too: charge it as scratch
        kwargs.setdefault("memory", self._sched.memory)
        return self._catalog.publish(*args, **kwargs)

    def invoke(self, *args, **kwargs) -> InvokeResult:
        return self._router.invoke(*args, **kwargs)

    def submit(self, *args, **kwargs):
        return self._router.submit(*args, **kwargs)

    def submit_invocation(self, inv: Invocation) -> InvocationHandle:
        """The typed v2 surface (QoS class, deadline, cancellation)."""
        return self._router.submit_invocation(inv)

    def close(self) -> None:
        self._router.close()

    def evict(self, fname: Optional[str] = None) -> None:
        self._sched.evict(fname)

    def record_access(self, fname, *args, **kwargs):
        return self._catalog.record_access(fname, self._sched, *args, **kwargs)

    def relayout(self, fname, order=None):
        return self._catalog.relayout(fname, order=order, node=self._sched)
