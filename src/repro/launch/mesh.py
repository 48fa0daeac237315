"""Production mesh construction (single-host and multi-process).

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import, and smoke tests must keep seeing the single real device.

The mesh axis NAMES live here as the module constants ``POD_AXIS`` /
``DATA_AXIS`` / ``MODEL_AXIS``. Collective call sites (``psum`` / ``pmean``
/ ``all_gather`` / ...) must reference these constants rather than spelling
the strings inline — enforced by lint rule ``axis-name-literal`` — so a
mesh-layout rename is one edit, not a repo-wide grep.

Multi-process: :func:`init_distributed` (routed through
:mod:`repro.core.compat`) brings up the ``jax.distributed`` runtime, after
which :func:`make_pod_mesh` lays the ``pod`` axis over processes.
:func:`make_local_mesh` builds the per-process compute mesh for backends
(CPU) whose collectives cannot cross processes.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType

# The canonical mesh axis names. Every psum/pmean/all_gather axis argument
# in src/ traces back to these (lint rule axis-name-literal).
POD_AXIS = "pod"
DATA_AXIS = "data"
MODEL_AXIS = "model"


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None) -> None:
    """Bring up the multi-process jax runtime (idempotent).

    Thin wrapper over :func:`repro.core.compat.distributed_initialize` — the
    version shim owns the actual ``jax.distributed.initialize`` call. With
    no arguments jax auto-detects the cluster environment (SLURM etc.); an
    explicit (coordinator, n, id) triple is what the tests and ad-hoc
    launches pass. Call BEFORE any jax device use, then build the
    process-spanning mesh with :func:`make_pod_mesh`.
    """
    from repro.core.compat import distributed_initialize
    distributed_initialize(coordinator_address=coordinator_address,
                           num_processes=num_processes,
                           process_id=process_id)


def _make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the pjit rules
    (``sharding/rules.py`` + ``with_sharding_constraint`` hints) leave the
    partitioning of intermediates to GSPMD, and ``shard_map`` regions name
    their axes explicitly, so neither path wants the sharding-in-types
    ``Explicit`` axes that jax >= 0.7 picks when ``axis_types`` is omitted."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 two-pod (512 chips) mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ((POD_AXIS, DATA_AXIS, MODEL_AXIS) if multi_pod
            else (DATA_AXIS, MODEL_AXIS))
    return _make_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1x1 mesh on the real local device (CPU smoke tests)."""
    return _make_mesh((1, 1), (DATA_AXIS, MODEL_AXIS))


def make_data_mesh(n_devices: int = 0):
    """1-D ("data",) mesh over all (or the first ``n_devices``) local
    devices — one mesh slot per GBN device shard; used by the shard_map
    data-parallel trainer (:mod:`repro.train.data_parallel`)."""
    n = n_devices or len(jax.devices())
    return _make_mesh((n,), (DATA_AXIS,))


def make_2d_mesh(n_devices: int = 0, model: int = 0):
    """2-D ("data", "model") mesh over the local devices — the small-scale
    twin of :func:`make_production_mesh`, used by the unified parallelism
    layer (:mod:`repro.train.parallel`) and the experiments runner.

    ``model=0`` picks the model-axis size automatically: 2 when the device
    count is even (the smallest non-degenerate model axis — expert shards
    stay coarse, dp stays wide), else 1.
    """
    n = n_devices or len(jax.devices())
    m = model or (2 if n > 1 and n % 2 == 0 else 1)
    if n % m:
        raise ValueError(f"{n} devices do not factor into model={m}")
    return _make_mesh((n // m, m), (DATA_AXIS, MODEL_AXIS))


def make_pod_mesh(model: int = 1):
    """3-D ("pod", "data", "model") mesh spanning ALL processes: one pod
    slot per process, ``data`` over each process's remaining devices.

    Requires :func:`init_distributed` first. ``jax.make_mesh`` enumerates
    devices process-major, so each pod row is exactly one process's local
    devices — the pod axis IS the process axis. Cross-pod collectives need
    a backend with inter-process transport (TPU/GPU); the CPU backend can
    build this mesh, create/checkpoint global arrays on it, but not run a
    computation across it (XLA: "Multiprocess computations aren't
    implemented on the CPU backend") — use :func:`make_local_mesh` for the
    per-host compute there.
    """
    nproc = jax.process_count()
    n = len(jax.devices())
    local = n // nproc
    if model <= 0 or local % model:
        raise ValueError(
            f"{local} per-process devices do not factor into model={model}")
    return _make_mesh((nproc, local // model, model),
                         (POD_AXIS, DATA_AXIS, MODEL_AXIS))


def make_local_mesh(model: int = 1):
    """2-D ("data", "model") mesh over THIS process's addressable devices.

    The per-host compute mesh under a multi-process runtime whose backend
    lacks cross-process collectives (CPU): each host trains/serves its own
    shard of the work (see ``run_sweep(shard=...)``) on its local devices
    while the process-spanning :func:`make_pod_mesh` handles global array
    placement and per-shard checkpointing.
    """
    import numpy as np
    devs = np.asarray(jax.local_devices())
    n = len(devs)
    if model <= 0 or n % model:
        raise ValueError(
            f"{n} local devices do not factor into model={model}")
    return jax.sharding.Mesh(devs.reshape(n // model, model),
                             (DATA_AXIS, MODEL_AXIS),
                             axis_types=(AxisType.Auto,) * 2)


def global_array(mesh, arr, spec):
    """A global jax.Array on ``mesh`` from a host-identical numpy array.

    Under a multi-process runtime a plain ``jnp.asarray`` is process-local
    and cannot feed a computation over a process-spanning mesh; this places
    each shard from the (identical on every host) ``arr`` — the standard
    way to feed replicated-input batches onto a pod mesh.
    """
    from jax.sharding import NamedSharding
    return jax.make_array_from_callback(
        arr.shape, NamedSharding(mesh, spec), lambda idx: arr[idx])


def dp_axes(mesh) -> Tuple[str, ...]:
    """The axes the global batch is sharded over (only those present)."""
    return tuple(a for a in (POD_AXIS, DATA_AXIS) if a in mesh.axis_names)


def dp_size(mesh) -> int:
    """Total data-parallel ways: the product of the present dp axis sizes."""
    n = 1
    for a in dp_axes(mesh):
        n *= mesh.shape[a]
    return n


def dp_spec_entry(mesh):
    """The dp axes as one PartitionSpec entry: None when the mesh has no
    data axes, the bare name for one, the tuple for several."""
    axes = dp_axes(mesh)
    if not axes:
        return None
    return axes if len(axes) > 1 else axes[0]


def fsdp_axes(mesh) -> Tuple[str, ...]:
    """The axes parameters are fully-sharded over (in addition to 'model')."""
    return ((DATA_AXIS, POD_AXIS) if POD_AXIS in mesh.axis_names
            else (DATA_AXIS,))


def axis_size(mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1
