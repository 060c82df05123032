"""Multi-device sharded serving: a per-host dispatcher over sharded
bucket programs.

The single-device serving stack multiplexes traffic onto jitted bucket
programs (serve/cnn.py) behind a continuous-batching scheduler
(serve/frontend.py).  This module scales that stack across *devices*
and *hosts* without changing what a bucket program is:

* **Sharded bucket programs.**  ``ShardedServeDispatcher`` builds its
  ``AsyncServeFrontend`` with a 1-D ``('data',)`` serve mesh
  (launch/mesh.make_serve_mesh), so every bucket program is the
  per-shard-geometry ``GraphPlan`` — tuned launch configs from
  autotune.json reused per shard unchanged — wrapped in ``shard_map``
  and jitted with the batch axis sharded.  Configured buckets are
  per-shard capacities; served (global) buckets are
  ``bucket × mesh_size``, device-count-aware by construction.  Because
  the per-shard body traces at the per-shard batch shape, outputs are
  bitwise-identical to the single-device engine at that bucket.

* **One param replication.**  ``dist.sharding.replicate_params`` moves
  the param tree onto the mesh exactly once (explicit ``device_put``
  with a replicated ``NamedSharding``); every geometry's programs share
  the replicated tree by reference and a warm serve loop runs clean
  under ``jax.transfer_guard("disallow")``.

* **Logical engine partitions.**  The dispatcher exposes one logical
  partition per mesh device: ``partitions()`` reports each device's
  real-image count and slot utilization (padding concentrates in the
  trailing shards), and ``stats()["sharding"]`` carries the
  shard-imbalance counters rolled up in serve/telemetry.py.

* **Scale-out seam.**  Admission is ``process_index``-disciplined: a
  multi-process deployment runs ONE dispatcher per host, and
  ``owned_geometries`` deterministically partitions the geometry table
  across processes (sorted round-robin) so every request geometry has
  exactly one owner — turning multi-host serving into a config change
  (launch/serve.py ``--cnn-dist``), in the spirit of the actor/learner
  split the ROADMAP cites.

On CPU CI the whole subsystem runs under
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` forced host
devices (tests/test_distributed_serve.py).
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List, Mapping, Optional, Tuple

import jax

from repro.dist.sharding import replicate_params
from repro.launch.mesh import make_serve_mesh
from repro.serve.frontend import AsyncServeFrontend, ServeRequest


def owned_geometries(geometries: Mapping[Tuple[int, int, int],
                                         Tuple[int, ...]],
                     process_index: int, process_count: int
                     ) -> Dict[Tuple[int, int, int], Tuple[int, ...]]:
    """Deterministic per-host ownership of the geometry table.

    Geometries are sorted and dealt round-robin, so every process
    derives the same partition from the same config with no
    coordination, every geometry has exactly one owner, and adding a
    host is a config change.  A process may own nothing (more hosts
    than geometries) — its dispatcher idles.
    """
    if not 0 <= process_index < process_count:
        raise ValueError(f"process_index {process_index} not in "
                         f"[0, {process_count})")
    items = sorted((tuple(map(int, s)), tuple(b))
                   for s, b in dict(geometries).items())
    return {shape: buckets for i, (shape, buckets) in enumerate(items)
            if i % process_count == process_index}


class ShardedServeDispatcher:
    """Per-host dispatcher: sharded bucket programs behind the async
    scheduler.

    Reuses ``AsyncServeFrontend``'s admission/EDF/SLO/telemetry
    machinery wholesale — the dispatcher owns the mesh, the one-time
    param replication, the host's geometry ownership, and the
    per-device accounting on top.  ``mesh=None`` forms the serve mesh
    over every addressable device (1 device ⇒ behaves exactly like the
    plain frontend, same scheduler states).
    """

    def __init__(self, model, params,
                 geometries: Mapping[Tuple[int, int, int],
                                     Tuple[int, ...]], *,
                 mesh=None, process_index: Optional[int] = None,
                 process_count: Optional[int] = None,
                 max_wait_ms: float = 2.0,
                 default_deadline_ms: Optional[float] = None,
                 slo_close_margin_ms: float = 0.0,
                 pipeline_depth: int = 2, algorithm="auto",
                 backend: Optional[str] = None, precision=None,
                 fuse: bool = True, input_dtype=None,
                 clock: Callable[[], float] = time.perf_counter):
        self.mesh = mesh if mesh is not None else make_serve_mesh()
        self.n_devices = int(self.mesh.devices.size)
        self.process_index = (jax.process_index() if process_index is None
                              else int(process_index))
        self.process_count = (jax.process_count() if process_count is None
                              else int(process_count))
        self.owned = owned_geometries(geometries, self.process_index,
                                      self.process_count)
        # ONE explicit replication; every geometry's BucketPrograms sees
        # already-replicated leaves and passes them through untouched
        self.params = replicate_params(params, self.mesh)
        self.model = model
        self.frontend: Optional[AsyncServeFrontend] = None
        if self.owned:
            self.frontend = AsyncServeFrontend(
                model, self.params, self.owned,
                max_wait_ms=max_wait_ms,
                default_deadline_ms=default_deadline_ms,
                slo_close_margin_ms=slo_close_margin_ms,
                pipeline_depth=pipeline_depth, algorithm=algorithm,
                backend=backend, precision=precision, fuse=fuse,
                input_dtype=input_dtype, mesh=self.mesh, clock=clock)

    # ------------------------------------------------------------------
    @property
    def geometries(self) -> Tuple[Tuple[int, int, int], ...]:
        """The geometries THIS host owns (its admission surface)."""
        return tuple(self.owned)

    def global_buckets(self, shape) -> Tuple[int, ...]:
        """The device-count-aware (global) bucket sizes serving one
        owned geometry — per-shard config × mesh size."""
        return self.frontend.programs[tuple(map(int, shape))].buckets

    def warmup(self, *, measure: bool = False,
               tune: Optional[str] = None) -> Dict[str, Dict[int, float]]:
        if self.frontend is None:
            return {}
        return self.frontend.warmup(measure=measure, tune=tune)

    # -- serving entry points (the frontend's, ownership-checked) -------
    def submit(self, req: ServeRequest) -> None:
        """Admit a request this host owns.  A geometry owned by a
        different process is a routing error, named as such — the
        deterministic ownership rule means the caller can compute the
        right host without asking anyone."""
        if self.frontend is not None:
            shape = tuple(req.images.shape[1:])
            if shape in self.owned:
                return self.frontend.submit(req)
        raise ValueError(
            f"request {req.rid}: geometry {tuple(req.images.shape[1:])} "
            f"is not owned by process {self.process_index}/"
            f"{self.process_count} (owned: {list(self.owned)})")

    def poll(self) -> List[ServeRequest]:
        return [] if self.frontend is None else self.frontend.poll()

    def flush(self) -> List[ServeRequest]:
        return [] if self.frontend is None else self.frontend.flush()

    def run(self) -> List[ServeRequest]:
        return [] if self.frontend is None else self.frontend.run()

    # -- observability ---------------------------------------------------
    def partitions(self) -> List[Dict]:
        """One logical engine partition per mesh device: which device,
        how many real images it computed, and its slot utilization."""
        shard = (self.frontend.telemetry.shard_rollup()
                 if self.frontend is not None else None)
        out = []
        for i, dev in enumerate(self.mesh.devices.flat):
            units = shard["per_device_units"][i] if shard else 0
            util = shard["per_device_utilization"][i] if shard else 0.0
            out.append({"partition": i, "device": str(dev),
                        "units": units, "utilization": util})
        return out

    def stats(self) -> Dict:
        """The frontend's JSON-ready rollup plus the mesh/ownership
        view: device count, per-partition utilization, shard-imbalance
        counters, and this host's slice of the deployment."""
        st = self.frontend.stats() if self.frontend is not None else {
            "requests": 0, "served": 0, "geometries": []}
        st.update({
            "process_index": self.process_index,
            "process_count": self.process_count,
            "devices": self.n_devices,
            "partitions": self.partitions(),
            "global_buckets": {
                "x".join(map(str, s)): list(self.global_buckets(s))
                for s in self.owned},
        })
        return st
