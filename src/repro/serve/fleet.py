"""Fleet serving: a multi-tenant SLO-aware router over a pipeline pool.

The async engine serves exactly one :class:`~repro.api.build.FrozenPipeline`;
a deployment serves the paper's whole accuracy/throughput ladder behind
one front door.  :class:`PipelineFleet` is that front door:

* **Pipeline pool** — N built pipelines (different specs / precisions /
  variants, each possibly replicated), built without re-tracing shared
  structure by ``repro.api.build.build_pool`` and placed over a 2-D
  ``("replica", "data")`` device mesh when sharded
  (``repro.serve.sharding.make_mesh2d``).  Each pool member gets its
  own :class:`~repro.serve.async_engine.AsyncPointCloudEngine` on a
  shared clock and seed.
* **Tenant routing** — requests arrive as ``submit(tenant, cloud)``;
  the tenant's declarative :class:`~repro.api.spec.TenantSpec` names
  its tier (a pool pipeline), and the fleet's router
  (``repro.serve.router.ROUTERS``, named by ``FleetSpec.router``)
  picks a replica among that tier from queue-pressure snapshots.
* **Admission control** — before queueing, the request passes the
  :class:`~repro.serve.admission.AdmissionController`: the tenant's
  ``max_inflight`` bulkhead, and — when the replica's calibrated
  ``CostModelBatch`` can price the backlog — the tenant's ``slo_ms``.
  A refusal raises a typed
  :class:`~repro.serve.admission.Overloaded` *before* any future
  exists: a shed request can never hang and never steals a dispatch
  lane from admitted traffic.

Result invariance is inherited, not re-proven: every replica engine
restarts each dispatch from the shared seed LFSR state, so a tenant's
logits are bit-identical to serving the same clouds through its
tier's pipeline alone — no matter which replica the router picked,
what was co-batched, or how the 2-D mesh split the dispatch
(``tests/serving/test_fleet.py`` pins this golden equivalence).

Driving it mirrors one engine — sans-IO and deterministic::

    fleet = PipelineFleet.from_specs(fleet_spec, params_by_name,
                                     clock=virtual_clock)
    fut = fleet.submit("lidar-rt", cloud)     # may raise Overloaded
    fleet.pump(); fleet.flush()

or under asyncio: ``serve_loop()`` pumps every replica on one ticking
task, ``classify_async(tenant, cloud)`` awaits one answer, ``close()``
drains and exits.
"""
from __future__ import annotations

import asyncio
import collections
import dataclasses
import time
from typing import Callable, Dict, List, Mapping, Sequence

import numpy as np

from repro.api.build import FrozenPipeline, build_pool
from repro.api.spec import FleetSpec, TenantSpec
from repro.serve.admission import AdmissionController, Overloaded
from repro.serve.async_engine import AsyncPointCloudEngine, ServeFuture
from repro.serve.router import ROUTERS, ReplicaView, route

__all__ = ["PipelineFleet", "Replica", "TenantState", "Overloaded"]


@dataclasses.dataclass
class Replica:
    """One pool slot: a built pipeline plus its private engine."""
    replica_id: int
    tier: str                      # the pipeline spec's name
    engine: AsyncPointCloudEngine

    def view(self) -> ReplicaView:
        """Queue-pressure snapshot handed to routers/admission."""
        return ReplicaView(replica_id=self.replica_id, tier=self.tier,
                           depth=self.engine.depth,
                           pending=self.engine.pending,
                           max_batch=self.engine.max_batch)


@dataclasses.dataclass
class TenantState:
    """Live accounting for one tenant (spec is the declarative part)."""
    spec: TenantSpec
    submitted: int = 0             # admitted requests
    shed: int = 0                  # Overloaded rejections
    inflight: int = 0              # admitted, not yet resolved
    router_state: dict = dataclasses.field(default_factory=dict)
    latencies_ms: collections.deque = dataclasses.field(
        default_factory=lambda: collections.deque(maxlen=10_000))

    @property
    def shed_rate(self) -> float:
        """Shed fraction of everything offered (admitted + shed)."""
        offered = self.submitted + self.shed
        return self.shed / offered if offered else 0.0


class PipelineFleet:
    """Multi-tenant serving over a pool of frozen pipelines.

    Args:
      pool: one built :class:`FrozenPipeline` per replica, in
        ``fleet_spec.pool_specs()`` order (use :meth:`from_specs` to
        build pool + mesh from the spec in one call).
      fleet_spec: the declarative deployment (tenants, tiers, router,
        ``max_batch``).
      seed: LFSR seed shared by every replica engine — the same seed a
        solo engine would use, which is what makes per-tenant results
        replica-invariant.
      clock: monotonic seconds source shared by every engine and all
        tenant timing (injectable; the virtual-clock harness drives it).
      calibrate_every: forwarded to each replica engine's periodic
        cost-model recalibration (dispatches per sliding window).
    """

    def __init__(self, pool: Sequence[FrozenPipeline],
                 fleet_spec: FleetSpec, *, seed: int = 0,
                 clock: Callable[[], float] = time.monotonic,
                 calibrate_every: int = 64):
        specs = fleet_spec.pool_specs()
        if len(pool) != len(specs):
            raise ValueError(
                f"pool has {len(pool)} pipelines but the fleet spec "
                f"describes {len(specs)} replicas "
                f"({fleet_spec.replicas} x {len(fleet_spec.pipelines)} "
                f"pipelines)")
        for pipe, spec in zip(pool, specs):
            if pipe.spec.name != spec.name:
                raise ValueError(
                    f"pool order must match FleetSpec.pool_specs(): got "
                    f"pipeline {pipe.spec.name!r} in the "
                    f"{spec.name!r} slot")
        self.spec = fleet_spec
        self._router = ROUTERS.get(fleet_spec.router)
        self._admission = AdmissionController()
        self._clock = clock
        self.replicas: List[Replica] = [
            Replica(replica_id=i, tier=pipe.spec.name,
                    engine=AsyncPointCloudEngine(
                        pipe, max_batch=fleet_spec.max_batch, seed=seed,
                        clock=clock, calibrate_every=calibrate_every))
            for i, pipe in enumerate(pool)]
        self.tenants: Dict[str, TenantState] = {
            t.name: TenantState(spec=t) for t in fleet_spec.tenants}
        self._tier_replicas: Dict[str, List[Replica]] = {}
        for rep in self.replicas:
            self._tier_replicas.setdefault(rep.tier, []).append(rep)
        self._closed = False

    @classmethod
    def from_specs(cls, fleet_spec: FleetSpec,
                   params_by_name: Mapping[str, dict],
                   **kwargs) -> "PipelineFleet":
        """Build pool + mesh + fleet from the declarative spec alone."""
        fleet_spec.validate()
        pool = build_pool(fleet_spec.pool_specs(), params_by_name)
        return cls(pool, fleet_spec, **kwargs)

    # ------------------------------------------------------ sans-IO ----

    def _route_admit(self, tenant: str):
        """Shared route + admission front half of every submit path;
        returns ``(tenant_state, replica)`` or raises ``Overloaded`` /
        ``KeyError`` before any future exists."""
        if self._closed:
            raise RuntimeError("fleet is closed")
        try:
            state = self.tenants[tenant]
        except KeyError:
            raise KeyError(
                f"unknown tenant {tenant!r}; registered tenants: "
                f"{', '.join(sorted(self.tenants))}") from None
        candidates = self._tier_replicas[state.spec.tier]
        pick = route(self._router, tenant,
                     [r.view() for r in candidates], state.router_state)
        replica = self.replicas[pick]
        try:
            self._admission.check(state.spec, state.inflight,
                                  replica.view(), replica.engine.policy)
        except Overloaded:
            state.shed += 1
            raise
        return state, replica

    def _settle_admitted(self, state: TenantState,
                         fut: ServeFuture) -> ServeFuture:
        state.submitted += 1
        state.inflight += 1

        def settle(f: ServeFuture, _state=state) -> None:
            _state.inflight -= 1
            _state.latencies_ms.append(f.latency_ms)

        fut.add_done_callback(settle)
        return fut

    def submit(self, tenant: str, points, category=None) -> ServeFuture:
        """Route + admit one ``[N, 3]`` cloud for ``tenant`` (for a
        part-segmentation tier, its points and category).

        Returns the request's future on admission; raises
        :class:`Overloaded` on a shed (typed, counted in
        ``tenant_stats``, no future created) and ``KeyError`` for an
        unknown tenant.
        """
        state, replica = self._route_admit(tenant)
        return self._settle_admitted(state,
                                     replica.engine.submit(points, category))

    def open_stream(self, tenant: str, *, max_age=None):
        """A :class:`~repro.serve.streaming.AsyncStreamSession` for
        ``tenant`` over the fleet's routed submit path.

        Each frame routes and admits exactly like :meth:`submit` (an
        ``Overloaded`` shed leaves the session's cache state
        untouched).  The cache stays valid across replicas of the
        tenant's tier: replicas share spec, params, and seed, so a
        cache collected on one replica replays bit-identically on any
        other.  Requires the tier's spec to set ``stream=True``.
        """
        from repro.serve import streaming
        try:
            tstate = self.tenants[tenant]
        except KeyError:
            raise KeyError(
                f"unknown tenant {tenant!r}; registered tenants: "
                f"{', '.join(sorted(self.tenants))}") from None
        pipe = self._tier_replicas[tstate.spec.tier][0].engine.pipeline
        streaming._require_streaming(pipe)

        def submit_stream(cloud, cstate, hit):
            state, replica = self._route_admit(tenant)
            fut = replica.engine._submit_stream(cloud, cstate, hit)
            return self._settle_admitted(state, fut)

        return streaming.AsyncStreamSession(
            submit_stream, n_points=pipe.model_config.n_points,
            threshold=pipe.spec.stream_drift_threshold, max_age=max_age)

    def pump(self, block: bool = True) -> int:
        """One scheduler turn across the pool, in replica order;
        returns the total dispatched request count."""
        return sum(rep.engine.pump(block=block) for rep in self.replicas)

    def flush(self) -> None:
        """Drain every replica queue; all admitted futures resolve."""
        for rep in self.replicas:
            rep.engine.flush()

    @property
    def depth(self) -> int:
        """Queued (not yet dispatched) requests across the pool."""
        return sum(rep.engine.depth for rep in self.replicas)

    @property
    def pending(self) -> int:
        """Unresolved requests across the pool: queued + in flight."""
        return sum(rep.engine.pending for rep in self.replicas)

    def warmup(self) -> float:
        """Compile every distinct replica executable ahead of traffic
        (pool members sharing one pipeline compile once); returns
        total compile seconds."""
        seen, total = set(), 0.0
        for rep in self.replicas:
            key = id(rep.engine.pipeline)
            if key in seen:
                continue
            seen.add(key)
            total += rep.engine.warmup()
        return total

    def calibrate(self) -> int:
        """Force a cost-model refresh on every replica engine
        (each engine also recalibrates periodically on its own);
        returns how many accepted."""
        return sum(bool(rep.engine.calibrate_policy())
                   for rep in self.replicas)

    # -------------------------------------------------------- stats ----

    def stats(self) -> dict:
        """Aggregate pool counters (sums of the engines' stats)."""
        agg = {"requests": 0, "batches": 0, "padded": 0,
               "serve_s": 0.0, "host_s": 0.0, "compile_s": 0.0}
        for rep in self.replicas:
            s = rep.engine.stats
            agg["requests"] += s.requests
            agg["batches"] += s.batches
            agg["padded"] += s.padded
            agg["serve_s"] += s.serve_s
            agg["host_s"] += s.host_s
            agg["compile_s"] += s.compile_s
        agg["samples_per_s"] = (agg["requests"] / agg["serve_s"]
                                if agg["serve_s"] > 0 else 0.0)
        agg["shed"] = sum(t.shed for t in self.tenants.values())
        return agg

    def tenant_stats(self) -> Dict[str, dict]:
        """Per-tenant SLO accounting: volumes, shed rate, wait
        percentiles (ms, on the fleet clock)."""
        out = {}
        for name, state in self.tenants.items():
            lat = np.asarray(state.latencies_ms, dtype=np.float64)
            out[name] = {
                "tier": state.spec.tier,
                "slo_ms": state.spec.slo_ms,
                "submitted": state.submitted,
                "shed": state.shed,
                "shed_rate": state.shed_rate,
                "inflight": state.inflight,
                "p50_ms": float(np.percentile(lat, 50)) if lat.size else None,
                "p99_ms": float(np.percentile(lat, 99)) if lat.size else None,
            }
        return out

    def reset_stats(self) -> None:
        """Fresh measurement window across the pool and every tenant."""
        for rep in self.replicas:
            rep.engine.reset_stats()
        for state in self.tenants.values():
            state.submitted = 0
            state.shed = 0
            state.latencies_ms.clear()

    def describe(self) -> str:
        lines = [f"PipelineFleet({self.spec.name}): "
                 f"{len(self.replicas)} replicas "
                 f"({self.spec.replicas} x {len(self.spec.pipelines)} "
                 f"pipelines), router={self.spec.router}, "
                 f"max_batch={self.spec.max_batch}, "
                 f"data_shards={self.spec.data_shards}"]
        for rep in self.replicas:
            mesh = rep.engine.pipeline.mesh
            where = (f"devices {[d.id for d in mesh.devices.flat]}"
                     if mesh is not None else "single-device")
            lines.append(f"  replica {rep.replica_id}: tier={rep.tier} "
                         f"({where}); "
                         f"policy={rep.engine.policy.describe()}")
        for t in self.spec.tenants:
            lines.append(f"  tenant {t.name}: tier={t.tier} "
                         f"slo_ms={t.slo_ms:g} "
                         f"max_inflight={t.max_inflight}")
        return "\n".join(lines)

    # ------------------------------------------------ asyncio shell ----

    async def classify_async(self, tenant: str, points, category=None):
        """Submit one cloud for ``tenant`` and await its logits row, a
        read-only host ``np.ndarray`` (needs :meth:`serve_loop`
        running).  ``Overloaded`` propagates to the caller synchronously
        — shed is an answer, not a wait."""
        loop = asyncio.get_running_loop()
        afut = loop.create_future()

        def on_done(fut: ServeFuture) -> None:
            def settle() -> None:
                if not afut.done():
                    afut.set_result(fut.result())
            loop.call_soon_threadsafe(settle)

        self.submit(tenant, points, category).add_done_callback(on_done)
        return await afut

    async def serve_loop(self, tick_s: float = 0.001) -> None:
        """Background dispatcher: pump the whole pool every ``tick_s``
        until :meth:`close`, then flush (mirrors the single-engine
        loop — non-blocking pumps so device compute never stalls the
        event loop)."""
        while not self._closed:
            self.pump(block=False)
            await asyncio.sleep(tick_s)
        self.flush()

    def close(self) -> None:
        """Stop accepting requests; a running serve_loop flushes and
        exits.  Call ``flush()`` directly when driving sans-IO."""
        self._closed = True
        for rep in self.replicas:
            rep.engine.close()
