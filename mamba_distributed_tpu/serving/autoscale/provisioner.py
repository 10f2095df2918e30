"""Replica provisioners: how the autoscaler gets (and returns) capacity.

The ``AutoscaleController`` (serving/autoscale/controller.py) decides
WHEN the fleet grows or shrinks; a ``ReplicaProvisioner`` decides HOW a
replica comes into existence — the seam that lets the same policy loop
drive an in-process test fabric and a multi-process service fabric:

  * ``EngineProvisioner`` builds ``EngineReplica``s locally from shared
    params/config — the in-process fabric of tests/test_autoscale.py,
    where a "replica" costs one slot pool;
  * ``ProcessProvisioner`` wraps a spawn callable (the service path:
    ``scripts/serve_fabric.spawn_worker`` -> ``RemoteReplica``) and owns
    the worker-process lifecycle on retire.

Both honor the replica's tier ``role`` (serving/replica.REPLICA_ROLES),
so a disaggregated fabric's prefill and decode tiers size independently
— the controller asks for capacity IN a role, never a bare replica.
"""

from __future__ import annotations

from mamba_distributed_tpu.obs import NULL_TRACER
from mamba_distributed_tpu.serving.replica import REPLICA_ROLES, EngineReplica
from mamba_distributed_tpu.utils.metrics import ServingMetrics


class ReplicaProvisioner:
    """Interface: mint and retire replicas for the autoscaler.

    ``provision(replica_id, role)`` returns a replica ready for
    ``RequestRouter.add_replica`` (id MUST equal the router's next
    index — the controller passes ``len(router.replicas)``).
    ``retire(replica)`` releases whatever backs it AFTER the router has
    drained it to zero pending — the controller never retires a replica
    still holding streams."""

    def provision(self, replica_id: int, role: str):
        raise NotImplementedError

    def retire(self, replica) -> None:
        raise NotImplementedError


class EngineProvisioner(ReplicaProvisioner):
    """In-process replicas from shared weights: each ``provision`` is a
    fresh ``EngineReplica`` over the SAME read-only params (replicas
    cost slot pools, not param copies — serving/replica.py), with its
    own ``ServingMetrics`` stamped with the new replica id.

    Args:
      params / cfg: the fabric's shared weights and ModelConfig.
      capacity: slots per provisioned replica.
      tracer: SpanTracer each new engine writes to (the fabric-shared
        stream; per-replica streams are a ``spawn`` concern).
      session_store: shared durable-session store, when the fabric has
        one (new replicas must park/resume against the same tiers).
      engine_kw: forwarded to every new ServingEngine (tokens_per_tick,
        max_top_k, ...) — keep these identical to the seed replicas'
        or streams will not be placement-invariant.
    """

    def __init__(self, params, cfg, *, capacity: int = 8,
                 tracer=NULL_TRACER, session_store=None, **engine_kw):
        self.params = params
        self.cfg = cfg
        self.capacity = capacity
        self.tracer = tracer
        self.session_store = session_store
        self.engine_kw = engine_kw
        self.provisioned = 0
        self.retired = 0

    def provision(self, replica_id: int, role: str) -> EngineReplica:
        if role not in REPLICA_ROLES:
            raise ValueError(
                f"role must be one of {REPLICA_ROLES}, got {role!r}"
            )
        metrics = ServingMetrics(self.capacity, replica=replica_id)
        rep = EngineReplica(
            replica_id, self.params, self.cfg, metrics=metrics,
            tracer=self.tracer, role=role, capacity=self.capacity,
            retain_results=False,
            **({} if self.session_store is None
               else {"session_store": self.session_store}),
            **self.engine_kw,
        )
        self.provisioned += 1
        return rep

    def retire(self, replica) -> None:
        """Nothing to release: the engine's device buffers die with the
        last reference once the router drops the replica."""
        self.retired += 1


class ProcessProvisioner(ReplicaProvisioner):
    """Worker-process replicas behind a spawn callable — the service
    fabric's provisioner (scripts/serve_fabric.py builds the callable
    over ``spawn_worker`` + ``RemoteReplica``).

    Args:
      spawn: ``(replica_id, role) -> (proc, replica)`` — starts one
        worker process and returns its handle plus the connected
        ``RemoteReplica``.  ``proc`` may be None (externally-managed
        workers); only non-None procs are reaped on retire.
      shutdown_timeout_s: grace the retired worker process gets to exit
        after its shutdown RPC before being killed.
    """

    def __init__(self, spawn, *, shutdown_timeout_s: float = 30.0):
        self._spawn = spawn
        self.shutdown_timeout_s = shutdown_timeout_s
        self._procs: dict[int, object] = {}
        self.provisioned = 0
        self.retired = 0

    def provision(self, replica_id: int, role: str):
        if role not in REPLICA_ROLES:
            raise ValueError(
                f"role must be one of {REPLICA_ROLES}, got {role!r}"
            )
        proc, rep = self._spawn(replica_id, role)
        if proc is not None:
            self._procs[replica_id] = proc
        self.provisioned += 1
        return rep

    def retire(self, replica) -> None:
        """Shut the drained worker down (RPC first, then process reap);
        every step is best-effort — a worker that died on its own is
        already retired."""
        try:
            replica.shutdown()
        except Exception:  # noqa: BLE001 — already-dead worker
            pass
        proc = self._procs.pop(replica.replica_id, None)
        if proc is not None:
            try:
                proc.wait(timeout=self.shutdown_timeout_s)
            except Exception:  # noqa: BLE001 — wedged worker
                proc.kill()
        self.retired += 1
