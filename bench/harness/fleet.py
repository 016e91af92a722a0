"""Replicas behind the program's router, as the ``Driver`` drives them.

A cell with ``"replicas": {"n": 4, "policy": "affinity",
"max_queue_depth": 8}`` serves through ``n`` in-process
``EngineReplica``s, one pinned to each chip, behind ``FleetRouter``.  The
``Fleet`` gives the driver the surface of one ``ServingEngine``: ``submit``
routes a request and returns its ``RequestHandle``, ``step`` is
``FleetRouter.step_all``, and ``stats`` sums the replicas' scheduler
counters.

``FleetRouter.submit`` hands an in-process replica only ``(prompt,
params)`` and returns no handle, so a client's per-token clock would be
lost.  ``submit`` here places the request with the router's own
``route``, submits it to that replica's engine under the replica's device,
and records the placement as ``FleetRouter.submit`` does.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Sequence


class Pinned:
    """One engine whose every call runs under its own device, as a pinned
    ``EngineReplica`` runs its commands."""

    def __init__(self, engine, device):
        self.engine = engine
        self.device = device

    def submit(self, prompt, params=None):
        import jax
        with jax.default_device(self.device):
            return self.engine.submit(prompt, params=params)

    def step(self):
        import jax
        with jax.default_device(self.device):
            return self.engine.step()

    @property
    def idle(self) -> bool:
        return self.engine.idle

    @property
    def stats(self):
        return self.engine.stats


class SummedStats:
    """The replicas' ``SchedulerStats``, each counter summed."""

    def __init__(self, engines: Sequence[Any]):
        self._engines = list(engines)

    def __getattr__(self, name: str):
        return sum(getattr(e.stats, name) for e in self._engines)


class Fleet:
    """``n`` pinned in-process replicas behind ``FleetRouter``."""

    def __init__(self, builders: Sequence[Callable[[], Any]], devices,
                 policy: str = "affinity", max_queue_depth: int = 8):
        from repro.fleet.replica import EngineReplica
        from repro.fleet.router import FleetRouter
        self.replicas = [EngineReplica(b, replica_id=f"r{i}", device=d)
                         for i, (b, d) in enumerate(zip(builders, devices))]
        self.router = FleetRouter(self.replicas, policy=policy,
                                  max_queue_depth=int(max_queue_depth))
        self.engines = [r.engine for r in self.replicas]
        self.pinned = [Pinned(r.engine, r.device) for r in self.replicas]
        self.stats = SummedStats(self.engines)

    def submit(self, prompt, params=None):
        router = self.router
        p = router.route(router.namespace_of(params))
        handle = self.pinned[p.replica].submit(prompt, params=params)
        p.rid = handle.rid
        router.placements.append(p)
        return handle

    def step(self) -> None:
        self.router.step_all()

    @property
    def idle(self) -> bool:
        return all(e.idle for e in self.engines)

    def route_counts(self) -> Dict[str, float]:
        """The router's counters (requests routed, placed on their
        namespace's home replica, spilled)."""
        fs = self.router.fleet_stats()
        return {"routed": float(fs.routed),
                "affinity_hits": float(fs.affinity_hits),
                "spills": float(fs.spills)}

    def homes(self, namespaces: Sequence[str]) -> Dict[str, str]:
        return {ns: self.replicas[self.router.home_replica(ns)].replica_id
                for ns in namespaces}

    def placed(self) -> List[int]:
        """Requests placed on each replica so far."""
        out = [0] * len(self.replicas)
        for p in self.router.placements:
            out[p.replica] += 1
        return out
