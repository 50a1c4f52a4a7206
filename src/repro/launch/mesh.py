"""Production mesh construction + sweep mesh specs.

A function (not a module-level constant) so importing this module never
touches jax device state. Single pod: (16, 16) = 256 chips, axes
("data", "model"). Multi-pod: (2, 16, 16) = 512 chips with a leading "pod"
axis that is pure data-parallel — the only cross-pod collective in any of our
programs is the per-step gradient/residual all-reduce (which
repro.comm.compression can compress), so scaling beyond 2 pods = growing this
axis.

:class:`SweepMeshSpec` names how a scenario sweep maps onto a mesh: which
axes shard the event log and which (optional) axis shards the scenario grid —
the contract consumed by :func:`repro.core.sharded.sweep_sharded`. Axis
conventions are documented in docs/SCALING.md.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis explicitly ``AxisType.Auto`` (the
    one place this repo spells ``AxisType``)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests, elastic rescale)."""
    return _make_mesh(shape, axes)


def data_axes(mesh) -> tuple:
    """The event/batch axes of a mesh (everything except 'model')."""
    return tuple(a for a in mesh.axis_names if a != "model")


@dataclasses.dataclass(frozen=True)
class SweepMeshSpec:
    """How a scenario sweep maps onto a device mesh.

    * ``event_axes`` — mesh axes that shard the event (leading) dimension of
      the (N, C) valuation matrix, row-major in the given order (the same
      ordering contract as ``repro.core.sharded.shard_events``); campaign
      state stays replicated along them.
    * ``scenario_axis`` — optional mesh axis that shards the scenario grid:
      each slice of devices runs S / axis_size scenarios. ``None`` (default)
      keeps all scenarios vmapped on every event-shard.

    Frozen + hashable, so it can ride through ``jax.jit`` as a static
    argument. Build one with :meth:`for_devices` (host-platform meshes for
    tests/CI via ``XLA_FLAGS=--xla_force_host_platform_device_count=…``) or
    wrap an existing mesh directly.
    """

    mesh: jax.sharding.Mesh
    event_axes: Tuple[str, ...] = ("data",)
    scenario_axis: Optional[str] = None

    def __post_init__(self):
        names = set(self.mesh.axis_names)
        missing = [a for a in (*self.event_axes,
                               *((self.scenario_axis,)
                                 if self.scenario_axis else ()))
                   if a not in names]
        if missing:
            raise ValueError(
                f"mesh has axes {self.mesh.axis_names}; spec names "
                f"unknown axes {missing}")
        if self.scenario_axis in self.event_axes:
            raise ValueError(
                f"scenario_axis {self.scenario_axis!r} cannot also shard "
                "events")

    @property
    def event_device_count(self) -> int:
        size = 1
        for a in self.event_axes:
            size *= self.mesh.shape[a]
        return size

    @property
    def scenario_device_count(self) -> int:
        return self.mesh.shape[self.scenario_axis] if self.scenario_axis \
            else 1

    def local_event_count(self, n_events: int) -> int:
        """Events per device under this spec (the quantity chunk sizes must
        divide for chunking × sharding — see ``executor.check_chunks``)."""
        return n_events // self.event_device_count

    def plan(self, *, resolve: str = "auto", block_t: int = 256,
             interpret: Optional[bool] = None, skip_retired: bool = True,
             chunks=None, scenario_chunks=None):
        """Compose this mesh with the other execution axes into a
        :class:`repro.core.executor.SweepPlan` (placement ``"sharded"``).

        ``chunks`` (an int or :class:`~repro.core.executor.ChunkSpec`)
        states chunking × sharding: each device scans its own event shard
        ``events_per_chunk`` events at a time per Algorithm-2 round, so the
        per-device working set is bounded by the chunk, not the shard.
        Chunk sizes must divide :meth:`local_event_count` and hold whole
        canonical reduction blocks (pad-or-error at trace time).

        ``scenario_chunks`` (an int or
        :class:`~repro.core.executor.ScenarioChunkSpec`) does the same on
        the scenario axis: each device runs its scenario lanes
        ``scenarios_per_chunk`` at a time; sizes must divide the per-device
        scenario count (S / scenario-axis size).
        """
        from repro.core.executor import (SweepPlan, as_chunk_spec,
                                         as_scenario_chunk_spec)
        return SweepPlan(placement="sharded", mesh=self, resolve=resolve,
                         block_t=block_t, interpret=interpret,
                         skip_retired=skip_retired,
                         chunks=as_chunk_spec(chunks),
                         scenario_chunks=as_scenario_chunk_spec(
                             scenario_chunks))

    @property
    def is_multiprocess(self) -> bool:
        """Whether this spec's mesh spans more than one jax process."""
        return len({d.process_index for d in self.mesh.devices.flat}) > 1

    @staticmethod
    def for_processes() -> "SweepMeshSpec":
        """A multi-host sweep mesh: EVERY process's devices on one event axis.

        The contract ``placement="multihost"`` consumes: ``jax.devices()``
        enumerates devices process-major (process 0's devices first), so
        process ``r``'s event shard is the ``r``-th contiguous row-slice of
        the global log — the identical row-major ``index_offset`` placement
        a single-process mesh gives its devices, which is what makes the
        multihost run bit-for-bit the single-process sharded run on the
        same log. Degenerates to :meth:`for_devices` under one process
        (the wiring/bitwise tests run there). Call
        :func:`repro.compat.distributed_initialize` first on a real
        multi-process job; scenario-axis process meshes are not supported
        (shard scenarios *within* a process via ``placement="sharded"``).
        """
        devices = jax.devices()
        ranks = [d.process_index for d in devices]
        if ranks != sorted(ranks):  # pragma: no cover - jax orders by rank
            raise ValueError(
                "jax.devices() is not process-major on this backend; the "
                "multihost event-offset contract needs process r's devices "
                "to form the r-th contiguous slice of the mesh")
        mesh = _make_mesh((len(devices),), ("data",))
        return SweepMeshSpec(mesh, event_axes=("data",))

    @staticmethod
    def for_devices(num_event_devices: Optional[int] = None,
                    num_scenario_devices: int = 1) -> "SweepMeshSpec":
        """A sweep mesh over the locally visible devices.

        Defaults to all devices on the event axis; pass
        ``num_scenario_devices > 1`` to split off a trailing "model" axis for
        the scenario grid (total devices = event × scenario).
        """
        n_total = len(jax.devices())
        if num_scenario_devices < 1:
            raise ValueError(
                f"num_scenario_devices must be >= 1, got "
                f"{num_scenario_devices}")
        if num_event_devices is None:
            if n_total % num_scenario_devices != 0:
                raise ValueError(
                    f"{n_total} visible devices do not split into scenario "
                    f"groups of {num_scenario_devices}; pass "
                    "num_event_devices explicitly")
            num_event_devices = n_total // num_scenario_devices
        if num_event_devices < 1 or \
                num_event_devices * num_scenario_devices > n_total:
            raise ValueError(
                f"asked for {num_event_devices}×{num_scenario_devices} "
                f"devices but only {n_total} are visible")
        if num_scenario_devices > 1:
            mesh = _make_mesh((num_event_devices, num_scenario_devices),
                              ("data", "model"))
            return SweepMeshSpec(mesh, event_axes=("data",),
                                 scenario_axis="model")
        mesh = _make_mesh((num_event_devices,), ("data",))
        return SweepMeshSpec(mesh, event_axes=("data",))
