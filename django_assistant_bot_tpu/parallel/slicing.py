"""Device-slice planning: partition the host's devices into disjoint
per-replica submeshes so a fleet's aggregate throughput scales with chips.

Until now every :class:`~..serving.engine.GenerationEngine` replica traced
onto the SAME global mesh in one process: N replicas cost N KV pools on the
same chips, their ticks serialize on the same cores, and aggregate tok/s does
not rise with device count.  The reference's "scale" plane was N stateless
GPU-service pods behind HTTP (PAPER.md §7); the TPU-native equivalent is
replica-per-mesh-slice — :class:`MeshPlanner` cuts ``jax.devices()`` into
``n_devices // replica_devices`` disjoint :class:`DeviceSlice` submeshes,
each with tensor parallelism INSIDE the slice (``model`` is the innermost
mesh axis, so TP collectives ride neighbouring ICI links, exactly as the
global mesh recipe in parallel/mesh.py), and the serving registry pins each
replica's weights, KV page pool, and compiled programs to its own slice
(serving/registry.py; docs/MULTICHIP.md).

Lifecycle contract:

- ``acquire()`` hands out the lowest-numbered free slice; when every slice is
  taken it raises :class:`NoCapacity` — the router's ``add_replica`` (and the
  SLO autoscaler behind it) surface that as an honest "at hardware limit"
  decision instead of cloning another cache onto already-busy chips.
- ``release()`` returns a slice to the pool (replica detach / scale-down);
  releases are idempotent so a detach epilogue racing an engine teardown
  cannot double-free.
- Slices never overlap and never migrate: a replica keeps its slice across
  crash-only restarts (the restarted replica rebuilds ONLY its own slice's
  pool — other slices' warm KV is untouched, tests/test_slicing.py).

CPU recipe (tests, CI, the MULTICHIP dryrun): force a fake 8-device host with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (or
``jax.config.update("jax_num_cpu_devices", 8)``) and every slice is a real
submesh with real XLA collectives inside it.
"""

from __future__ import annotations

import dataclasses
import logging
import threading
from typing import List, Optional, Sequence

from jax.sharding import Mesh

from .mesh import best_mesh_shape, make_mesh

logger = logging.getLogger(__name__)


class NoCapacity(RuntimeError):
    """Every device slice is already pinned to a replica.

    Carries the planner's shape so the autoscaler / operator surface can say
    "at hardware limit" with numbers instead of a bare failure."""

    def __init__(self, msg: str, *, slices_total: int = 0, replica_devices: int = 0):
        super().__init__(msg)
        self.slices_total = slices_total
        self.replica_devices = replica_devices


@dataclasses.dataclass(frozen=True)
class DeviceSlice:
    """One replica's disjoint share of the host: a slice id, the devices it
    owns, and the submesh built over exactly those devices."""

    slice_id: int
    devices: tuple  # tuple[jax.Device, ...]
    mesh: Mesh

    @property
    def device_ids(self) -> List[int]:
        return [d.id for d in self.devices]


class MeshPlanner:
    """Partition a device list into fixed, disjoint per-replica slices.

    ``replica_devices`` is the topology knob (ModelSpec.replica_devices):
    e.g. 8 devices at ``replica_devices=2`` -> 4 replicas x TP-2.  Within a
    slice the mesh shape follows the global recipe — ``want_model`` defaults
    to the whole slice (pure tensor parallelism, the layout the MULTICHIP
    dryrun exercises at 8B geometry); pass a smaller degree to give the
    remainder to ``data``.

    Thread-safe: ``acquire``/``release`` are called from the registry's boot
    path, the router's scale-up factory (autoscaler thread), and the
    scale-down detach epilogue concurrently.  The lock is a leaf — nothing is
    called out of this class while it is held.
    """

    def __init__(
        self,
        replica_devices: int,
        *,
        devices: Optional[Sequence] = None,
        want_model: int = 0,
        want_seq: int = 1,
        want_expert: int = 1,
    ):
        import jax

        devices = list(devices if devices is not None else jax.devices())
        replica_devices = int(replica_devices)
        if replica_devices < 1:
            raise ValueError(
                f"replica_devices must be >= 1 (got {replica_devices})"
            )
        if replica_devices > len(devices):
            raise ValueError(
                f"replica_devices={replica_devices} exceeds the "
                f"{len(devices)} available device(s)"
            )
        self.replica_devices = replica_devices
        n_slices = len(devices) // replica_devices
        leftover = len(devices) - n_slices * replica_devices
        if leftover:
            # slices are fixed-size and disjoint; a non-dividing knob leaves
            # devices idle — say so loudly, it is almost never intentional
            logger.warning(
                "mesh planner: replica_devices=%d leaves %d of %d device(s) "
                "unused (%d slice(s) planned)",
                replica_devices,
                leftover,
                len(devices),
                n_slices,
            )
        axes = best_mesh_shape(
            replica_devices,
            want_model=want_model or replica_devices,
            want_seq=want_seq,
            want_expert=want_expert,
        )
        self.slice_axes = axes
        self._slices: List[DeviceSlice] = []
        for i in range(n_slices):
            devs = tuple(devices[i * replica_devices : (i + 1) * replica_devices])
            self._slices.append(
                DeviceSlice(
                    slice_id=i,
                    devices=devs,
                    mesh=make_mesh(axes, devices=devs),
                )
            )
        self._lock = threading.Lock()
        self._in_use: set = set()  # slice ids
        if n_slices > 1 and replica_devices > 1 and devices[0].platform == "tpu":
            # Measured on four v5e chips (JAX 0.9.0, libtpu 0.0.34; CHANGES.md
            # PR 21): every program of a TP-2 replica on devices [2, 3] halted
            # at launch ("The program continuator has halted unexpectedly";
            # once "schecklt: Invalid logical z: enhanced-barrier-parent-
            # phase-1") whenever its executable was LOADED from the persistent
            # compile cache, 4 runs of 4, and ran whenever it was compiled
            # fresh, 2 runs of 2; devices [0, 1] and all four were fine either
            # way.  Supervision restarts the replica and the router re-routes
            # its requests, so only the restart counters show it.  Until that
            # is understood, a process that carves multi-chip slices compiles
            # everything fresh.
            from ..utils.compile_cache import disable_persistent_compile_cache

            disable_persistent_compile_cache(
                f"{n_slices} device slices of {replica_devices} TPU chips: "
                "executables for a slice without chip 0 halt at launch when "
                "loaded from the cache (parallel/slicing.py)"
            )

    @property
    def n_slices(self) -> int:
        return len(self._slices)

    @property
    def slices(self) -> List[DeviceSlice]:
        return list(self._slices)

    def free_slices(self) -> int:
        with self._lock:
            return len(self._slices) - len(self._in_use)

    def acquire(self) -> DeviceSlice:
        """Pin the lowest-numbered free slice; raises :class:`NoCapacity`
        when the host is fully subscribed (the honest scale-up ceiling)."""
        with self._lock:
            for sl in self._slices:
                if sl.slice_id not in self._in_use:
                    self._in_use.add(sl.slice_id)
                    return sl
        raise NoCapacity(
            f"all {len(self._slices)} device slice(s) of "
            f"{self.replica_devices} device(s) are pinned to replicas",
            slices_total=len(self._slices),
            replica_devices=self.replica_devices,
        )

    def release(self, sl: DeviceSlice) -> None:
        """Return a slice to the pool.  Idempotent: a second release of the
        same slice (detach epilogue racing teardown) is a logged no-op."""
        with self._lock:
            if sl.slice_id not in self._in_use:
                logger.warning(
                    "mesh planner: slice %d released twice (ignored)",
                    sl.slice_id,
                )
                return
            self._in_use.discard(sl.slice_id)

    def stats(self) -> dict:
        """JSON-able snapshot for /healthz and /metrics: how many slices
        exist, how many are free, and the per-slice device pinning."""
        with self._lock:
            in_use = set(self._in_use)
        return {
            "replica_devices": self.replica_devices,
            "slices_total": len(self._slices),
            "slices_free": len(self._slices) - len(in_use),
            "slice_axes": {
                "data": self.slice_axes.data,
                "seq": self.slice_axes.seq,
                "model": self.slice_axes.model,
                "expert": self.slice_axes.expert,
                "pipe": self.slice_axes.pipe,
            },
            "slices": [
                {
                    "slice_id": sl.slice_id,
                    "devices": sl.device_ids,
                    "in_use": sl.slice_id in in_use,
                }
                for sl in self._slices
            ],
        }
