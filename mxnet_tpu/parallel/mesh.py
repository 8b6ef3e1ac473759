"""Device-mesh management — the TPU-native replacement for the reference's
device taxonomy (``include/mxnet/base.h:90 Context`` + ``group2ctx`` model
parallel placement, ``python/mxnet/symbol/symbol.py:1554``).

Where MXNet scattered arrays over an explicit ``[mx.gpu(0), mx.gpu(1), ...]``
list and hand-aggregated with kvstore reduce trees (``src/kvstore/comm.h:452``),
the TPU design names the axes of a single logical ``jax.sharding.Mesh`` and
lets GSPMD insert the collectives. Canonical axis names:

- ``dp``   data parallel (batch split; grad psum rides ICI)
- ``fsdp`` fully-sharded data parallel (params sharded over the dp group)
- ``tp``   tensor/model parallel (Megatron column/row splits)
- ``pp``   pipeline parallel (layer stages)
- ``sp``   sequence/context parallel (ring attention)
- ``ep``   expert parallel (MoE all_to_all)
"""
from __future__ import annotations

import contextlib
import re
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import jax

from ..base import FatalError
import numpy as onp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = [
    "MESH_AXES",
    "make_mesh",
    "current_mesh",
    "use_mesh",
    "named_sharding",
    "shard_params",
    "auto_shard_spec",
    "auto_degrade",
    "MeshDegradeError",
]

MESH_AXES = ("dp", "fsdp", "pp", "tp", "sp", "ep")


class _MeshStack(threading.local):
    def __init__(self):
        self.stack: List[Mesh] = []


_mesh_stack = _MeshStack()


def make_mesh(
    axes: Optional[Dict[str, int]] = None,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Build a named mesh over ``devices`` (default: all of them).

    ``axes`` maps axis name → size; at most one size may be ``-1`` meaning
    "all remaining devices". Default is a pure data-parallel mesh
    ``{"dp": -1}`` — the reference's only first-class strategy
    (SURVEY.md §2.3).
    """
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    if axes is None:
        axes = {"dp": -1}
    names = list(axes.keys())
    sizes = list(axes.values())
    n_fill = sizes.count(-1)
    if n_fill > 1:
        raise ValueError("at most one mesh axis may have size -1")
    fixed = 1
    for s in sizes:
        if s != -1:
            fixed *= s
    if n_fill:
        if len(devices) % fixed:
            raise ValueError(
                f"{len(devices)} devices not divisible by fixed axes {fixed}"
            )
        sizes[sizes.index(-1)] = len(devices) // fixed
    total = 1
    for s in sizes:
        total *= s
    if total != len(devices):
        # leaving chips idle silently is the classic half-capacity bug;
        # demand an exact factorization (or an explicit devices= subset)
        raise ValueError(
            f"mesh {dict(zip(names, sizes))} covers {total} devices but "
            f"{len(devices)} are available; use -1 for one axis or pass an "
            f"explicit devices= subset"
        )
    dev_array = onp.array(devices).reshape(sizes)
    return Mesh(dev_array, tuple(names))


def current_mesh() -> Optional[Mesh]:
    """Innermost active mesh (``use_mesh`` scope), else None."""
    if _mesh_stack.stack:
        return _mesh_stack.stack[-1]
    return None


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    """Scope a mesh as the default for parallel layers / Trainer / kvstore."""
    _mesh_stack.stack.append(mesh)
    try:
        with mesh:
            yield mesh
    finally:
        _mesh_stack.stack.pop()


def named_sharding(spec: PartitionSpec, mesh: Optional[Mesh] = None) -> NamedSharding:
    mesh = mesh or current_mesh()
    if mesh is None:
        raise ValueError("no active mesh; use use_mesh(...) or pass mesh=")
    # drop axes the mesh does not have (lets one spec serve dp-only and
    # dp x tp meshes alike)
    cleaned = []
    for entry in spec:
        if entry is None:
            cleaned.append(None)
        elif isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in mesh.axis_names)
            cleaned.append(kept if kept else None)
        else:
            cleaned.append(entry if entry in mesh.axis_names else None)
    return NamedSharding(mesh, PartitionSpec(*cleaned))


def match_rule(name: str, rules, default=PartitionSpec()):
    """First regex rule matching ``name`` wins; else ``default``."""
    for pat, spec in rules:
        if re.search(pat, name):
            return spec
    return default


def shard_params(
    params: Dict[str, jax.Array],
    rules: Sequence[Tuple[str, PartitionSpec]],
    mesh: Optional[Mesh] = None,
    default: PartitionSpec = PartitionSpec(),
) -> Dict[str, NamedSharding]:
    """Map parameter names to shardings via ordered regex rules — the
    jax-idiomatic version of the reference's per-key kvstore placement
    (``kvstore_dist.h:621 EncodeDefaultKey`` sharded big keys by hand).

    First matching rule wins; unmatched params get ``default`` (replicated).
    """
    mesh = mesh or current_mesh()
    return {
        name: named_sharding(match_rule(name, rules, default), mesh)
        for name in params
    }


class MeshDegradeError(FatalError):
    """No valid degraded mesh shape exists for the surviving device
    count — e.g. the preserved tp×pp product no longer fits. Fatal by
    design: resuming on a mesh that silently drops a model-parallel
    axis would load nonsense shards."""


def auto_degrade(
    axes: Dict[str, int],
    n_devices: int,
    *,
    power_of_two: bool = False,
    preserve: Sequence[str] = ("tp", "pp"),
) -> Tuple[Dict[str, int], int]:
    """Shrink a mesh shape onto ``n_devices`` survivors after rank loss.

    Degrade rule (the elastic fault-domain contract,
    ``docs/resilience.md``): axes in ``preserve`` (default tensor- and
    pipeline-parallel) keep their exact sizes — their sharded state
    cannot be re-tiled without a resharding pass — while the remaining
    axes (``dp`` first by convention, then ``fsdp``/``sp``/``ep`` in
    declaration order) absorb the loss. ``power_of_two=True`` further
    rounds the shrinkable budget down to a power of two (ring/butterfly
    collective layouts); survivors beyond the returned device count
    become spares.

    Returns ``(new_axes, devices_used)``. Raises
    :class:`MeshDegradeError` when no valid shape exists (preserved
    product exceeds the survivors, or the budget rounds to zero).
    """
    n_devices = int(n_devices)
    if n_devices < 1:
        raise MeshDegradeError("auto_degrade: no surviving devices")
    sizes = {a: int(s) for a, s in axes.items()}
    for a, s in sizes.items():
        if s < 1:
            raise ValueError(f"auto_degrade: axis {a!r} has size {s}; "
                             "resolve -1 axes before degrading")
    preserved = {a: s for a, s in sizes.items() if a in preserve}
    p = 1
    for s in preserved.values():
        p *= s
    if p > n_devices:
        raise MeshDegradeError(
            f"auto_degrade: preserved axes {preserved} need {p} devices "
            f"but only {n_devices} survive — no valid degraded shape "
            "(restore onto a bigger slice or reshard the model axes)")
    budget = n_devices // p
    if power_of_two:
        budget = 1 << (budget.bit_length() - 1)
    shrink = [a for a in sizes if a not in preserve]
    # first-listed shrink axis (dp by convention) absorbs the loss
    # before later ones are touched
    for i, a in enumerate(shrink):
        rest = 1
        for b in shrink[i + 1:]:
            rest *= sizes[b]
        if rest > budget:
            sizes[a] = 1
            continue
        sizes[a] = max(1, min(sizes[a], budget // rest))
    used = p
    for a in shrink:
        used *= sizes[a]
    if used > n_devices:
        # defensive only: the caps above guarantee the shrink product
        # fits the budget (every non-preserved axis, sp/ep included, is
        # shrunk — only `preserve` refuses), so this cannot fire unless
        # the loop invariant is broken by a future edit
        raise MeshDegradeError(
            f"auto_degrade: internal invariant broken — shape {sizes} "
            f"needs {used} devices with only {n_devices} surviving")
    return sizes, used


def auto_shard_spec(
    shape: Tuple[int, ...], axis_name: str = "fsdp", mesh: Optional[Mesh] = None
) -> PartitionSpec:
    """FSDP-style automatic spec: shard the largest dim divisible by the
    axis size, replicate if none qualifies (ZeRO-3 layout without a manual
    rule table)."""
    mesh = mesh or current_mesh()
    if mesh is None or axis_name not in mesh.axis_names:
        return PartitionSpec()
    size = mesh.shape[axis_name]
    order = sorted(range(len(shape)), key=lambda i: -shape[i])
    for i in order:
        if shape[i] % size == 0 and shape[i] >= size:
            entries = [None] * len(shape)
            entries[i] = axis_name
            return PartitionSpec(*entries)
    return PartitionSpec()
