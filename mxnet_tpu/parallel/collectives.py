"""Named-axis collectives — the TPU replacement for the reference's whole
communication stack: ``CommDevice`` flat allreduce (``src/kvstore/comm.h:452``),
``CommDeviceTree`` topology trees (``comm_tree.h:50``), NCCL
(``kvstore_nccl.h:285 ncclReduce / :402 ncclBcast``) and the ps-lite
push/pull RPC (``kvstore_dist.h:218``).

These are thin wrappers over ``jax.lax`` collectives: they only mean
something inside a ``shard_map``/``pjit`` region over a mesh with the named
axis — XLA lowers them onto ICI (intra-slice) or DCN (cross-slice)
automatically, which is the point: topology-aware routing is the compiler's
job here, not ``gpu_topology.h``'s.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

__all__ = [
    "allreduce",
    "allgather",
    "shard_map",
    "reduce_scatter",
    "broadcast",
    "ppermute",
    "ring_shift",
    "all_to_all",
    "axis_index",
    "axis_size",
    "pbroadcast_host",
    "barrier",
]


def shard_map(f, *, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with the replication check off by default —
    the one spelling every in-tree caller (ring attention, GPipe, syncbn
    tests) uses."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def allreduce(x, axis_name: str, op: str = "sum"):
    """In-graph all-reduce over a mesh axis (kvstore pushpull equivalent)."""
    if op == "sum":
        return lax.psum(x, axis_name)
    if op == "mean":
        return lax.pmean(x, axis_name)
    if op == "max":
        return lax.pmax(x, axis_name)
    if op == "min":
        return lax.pmin(x, axis_name)
    raise ValueError(f"unknown reduce op {op!r}")


def allgather(x, axis_name: str, axis: int = 0, tiled: bool = True):
    """Gather shards along ``axis`` from every member of the mesh axis."""
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name: str, axis: int = 0):
    """Sum over the axis group, then keep this member's shard — one hop of
    a bandwidth-optimal allreduce (what 2-level ``comm_tree.h`` approximated
    in software)."""
    return lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=True)


def broadcast(x, axis_name: str, src: int = 0):
    """Broadcast ``src``'s value to the whole axis group
    (``ncclBcast`` / kvstore ``broadcast`` parity)."""
    idx = lax.axis_index(axis_name)
    masked = jnp.where(idx == src, x, jnp.zeros_like(x))
    return lax.psum(masked, axis_name)


def ppermute(x, axis_name: str, perm: Sequence[Tuple[int, int]]):
    """Point-to-point permutation over the axis (ring attention's workhorse)."""
    return lax.ppermute(x, axis_name, perm=list(perm))


def ring_shift(x, axis_name: str, shift: int = 1, axis_size_hint: Optional[int] = None):
    """Rotate shards around the axis ring by ``shift`` (ICI-neighbor traffic)."""
    n = axis_size_hint or axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm=perm)


def all_to_all(x, axis_name: str, split_axis: int, concat_axis: int, tiled: bool = True):
    """All-to-all (expert-parallel dispatch / Ulysses head scatter)."""
    return lax.all_to_all(
        x, axis_name, split_axis=split_axis, concat_axis=concat_axis, tiled=tiled
    )


def axis_index(axis_name: str):
    return lax.axis_index(axis_name)


def axis_size(axis_name: str) -> int:
    if hasattr(lax, "axis_size"):
        return lax.axis_size(axis_name)
    return lax.psum(1, axis_name)


# -- host-level (outside jit; DCN control plane) ---------------------------

def pbroadcast_host(x, src_process: int = 0):
    """Broadcast a host value from one process to all (the role ps-lite's
    scheduler played for config distribution)."""
    from jax.experimental import multihost_utils

    return multihost_utils.broadcast_one_to_all(x, is_source=jax.process_index() == src_process)


def barrier(name: str = "mx_barrier"):
    """Cross-process sync point (reference ``kvstore.h:362
    barrier_before_exit``)."""
    if jax.process_count() == 1:
        return
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices(name)
