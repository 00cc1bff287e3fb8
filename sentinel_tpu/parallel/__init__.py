"""Multi-chip execution: device meshes and ICI collectives.

The reference scales cluster flow control through a Netty token server
(SURVEY.md §2.3); the TPU-native design replaces that RPC hop with XLA
collectives over ICI: every chip runs the same jitted flush on its shard
of the traffic against replicated counters, and window deltas +
cluster-global limits are combined with ``psum``/``pmax`` inside the
step (see :mod:`sentinel_tpu.parallel.ici`).
"""

from typing import Optional

from sentinel_tpu.parallel.mesh import make_mesh
from sentinel_tpu.parallel.ici import (
    merge_window_across,
    merge_stats_across,
    cluster_allocate,
    make_sharded_flush,
    batch_partition_specs,
)


def mesh_unavailable_reason(n_devices: int = 2) -> Optional[str]:
    """Why the sharded flush path cannot run in this environment (too few
    devices), or None when it can — callers, tests above all, skip with
    this reason."""
    import jax

    if len(jax.devices()) < n_devices:
        return (
            f"needs a {n_devices}-device mesh, environment has "
            f"{len(jax.devices())}"
        )
    return None


__all__ = [
    "make_mesh",
    "merge_window_across",
    "merge_stats_across",
    "cluster_allocate",
    "make_sharded_flush",
    "batch_partition_specs",
    "mesh_unavailable_reason",
]
