"""The repo's single import point for ``shard_map`` and the distributed
runtime.

``shard_map`` is re-exported from jax (0.9: ``jax.shard_map``, with the
``check_vma`` kwarg); lint rule ``shard-map-import`` keeps every call site
importing it from here, so a future move of the API is one edit.

``distributed_initialize`` is the one place the repo touches
``jax.distributed``. It is idempotent, so a launcher that already
initialized the runtime (SLURM plugin, test harness) composes with library
code that defensively calls it again.
"""
from __future__ import annotations

from typing import Optional

from jax import shard_map  # noqa: F401  (re-exported; see module docstring)

_DIST_INITIALIZED = False


def distributed_initialize(coordinator_address: Optional[str] = None,
                           num_processes: Optional[int] = None,
                           process_id: Optional[int] = None) -> None:
    """Idempotent ``jax.distributed.initialize``.

    ``None`` arguments leave jax's environment auto-detection to fill them
    in; a second call (from this shim or from an external launcher that
    beat us to it) is a no-op instead of the RuntimeError jax raises on
    double initialization. Must run before any jax device use.
    """
    global _DIST_INITIALIZED
    if _DIST_INITIALIZED:
        return
    import jax
    try:
        jax.distributed.initialize(coordinator_address=coordinator_address,
                                   num_processes=num_processes,
                                   process_id=process_id)
    except RuntimeError as e:
        # jax: "jax.distributed.initialize should only be called once"
        if "once" not in str(e):
            raise
    _DIST_INITIALIZED = True


def process_index() -> int:
    """This host's index in the distributed runtime (0 single-process)."""
    import jax
    return jax.process_index()


def process_count() -> int:
    """Number of processes in the distributed runtime (1 single-process)."""
    import jax
    return jax.process_count()
