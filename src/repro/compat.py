"""The one seam between this repo and the installed jax (0.9).

Every call whose spelling has moved between jax releases goes through here,
so the next move is a change in one place:

* ``shard_map`` — ``jax.shard_map`` with the replication (VMA) check off:
  our kernels return replicated (C,)-vectors from explicit psums, which the
  checker cannot always prove;
* ``axis_size`` — the size of a mapped mesh axis;
* multi-process helpers — ``jax.distributed`` initialisation (CPU runs need
  the gloo collectives implementation selected before init) and the
  host-local <-> global array conversions the ``multihost`` placement uses
  (:mod:`jax.experimental.multihost_utils`).
"""
from __future__ import annotations

import functools

import jax


def axis_size(axis_name):
    """Size of a mapped mesh axis, from inside shard_map/pmap."""
    return jax.lax.axis_size(axis_name)


def compiled_cost_analysis(compiled):
    """``Compiled.cost_analysis()`` as a flat dict."""
    return compiled.cost_analysis()


def process_count() -> int:
    """Number of jax processes (1 unless ``jax.distributed`` initialised)."""
    return jax.process_count()


def process_index() -> int:
    """This process's rank in the ``jax.distributed`` job (0 single-process)."""
    return jax.process_index()


def distributed_initialize(coordinator_address=None, num_processes=None,
                           process_id=None, *,
                           cpu_collectives: str = "gloo") -> None:
    """``jax.distributed.initialize`` with the CPU collectives backend
    selected first.

    On CPU the cross-process collectives implementation must be chosen
    *before* the backend initialises (jaxlib ships gloo). The knob is set
    unconditionally — probing the platform first (``jax.default_backend()``)
    would itself initialise the backend, which ``jax.distributed`` forbids;
    on TPU/GPU the runtime ignores it, so setting it is harmless.  All
    three address arguments may be ``None``, in which case jax falls back
    to its cluster auto-detection (the usual TPU-pod path).
    """
    if cpu_collectives:
        jax.config.update("jax_cpu_collectives_implementation",
                          cpu_collectives)
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)


def host_local_to_global(arr, mesh, pspec):
    """Assemble per-process host-local shards into one global ``jax.Array``.

    ``arr`` is this process's rows of the logical array under ``pspec`` on
    ``mesh`` (the whole array for replicated specs). Single-process meshes
    pass through with a plain sharded ``device_put``-equivalent — the
    degenerate case the multihost placement's bitwise tests pin.
    """
    from jax.experimental import multihost_utils
    return multihost_utils.host_local_array_to_global_array(arr, mesh, pspec)


def global_to_host_local(arr, mesh, pspec):
    """The inverse: a global array's process-local view under ``pspec``
    (the full logical value when replicated)."""
    from jax.experimental import multihost_utils
    return multihost_utils.global_array_to_host_local_array(arr, mesh, pspec)


def shard_map(fun=None, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication/VMA check disabled.

    Usable as a decorator factory exactly like the modern API:
    ``@functools.partial(shard_map, mesh=mesh, in_specs=..., out_specs=...)``.
    """
    if fun is None:
        return functools.partial(shard_map, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs)
    return jax.shard_map(fun, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
