"""Logical-axis sharding rules.

Model code annotates tensors with *logical* axis names
(``logical(x, "batch", "seq", "embed")``); the active ``ShardingRules`` maps
logical names to mesh axes.  Outside a ``use_sharding`` context every
annotation is a no-op, so the same model code runs single-device tests and
512-chip dry-runs unchanged.

Divisibility guard: a logical axis only binds to its mesh axes if the tensor
dimension is divisible by the mesh-axis-product; otherwise that dimension is
replicated (e.g. chatglm3's 2 KV heads on a 16-way model axis — standard
practice is KV replication when kv_heads < TP degree).
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

MeshAxes = Union[None, str, Tuple[str, ...]]


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    rules: Dict[str, MeshAxes]

    def resolve(self, name: Optional[str]) -> MeshAxes:
        if name is None:
            return None
        return self.rules.get(name)


# DP over (pod, data); TP/EP over model; SP (long-context cache) over data.
DEFAULT_RULES = ShardingRules(rules={
    "batch": ("pod", "data"),
    "batch_nopod": "data",
    "seq": None,
    "kv_seq": None,        # overridden to "data" for long-context decode (SP)
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "d_ff": "model",
    "experts": "model",
    "expert_ff": None,
    "vocab": "model",
    "ssm_heads": "model",
    "ssm_inner": "model",   # d_inner sharded on SSD-head boundaries
    "ssm_state": None,
    "fsdp": "data",        # parameter/optimizer-state sharding axis (ZeRO)
    "codebook": None,      # hash-decoder codebooks: replicated (tiny)
    "entities": None,      # packed code rows (override to "data" to shard
                           # the code buffer row-wise across hosts)
    "frontier": "data",    # unique-node decode frontier: data-parallel rows
})


class _State(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.rules: ShardingRules = DEFAULT_RULES


_STATE = _State()


@contextlib.contextmanager
def use_sharding(mesh: Optional[Mesh], rules: Optional[ShardingRules] = None):
    prev = (_STATE.mesh, _STATE.rules)
    _STATE.mesh = mesh
    _STATE.rules = rules or DEFAULT_RULES
    try:
        yield
    finally:
        _STATE.mesh, _STATE.rules = prev


def current_mesh() -> Optional[Mesh]:
    return _STATE.mesh


def current_rules() -> ShardingRules:
    return _STATE.rules


def _axis_size(mesh: Mesh, axes: MeshAxes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return size


def _spec_for(shape: Sequence[int], names: Sequence[Optional[str]]) -> Optional[P]:
    mesh = _STATE.mesh
    if mesh is None:
        return None
    rules = _STATE.rules
    parts = []
    used: set = set()
    for dim, name in zip(shape, names):
        axes = rules.resolve(name)
        if axes is None:
            parts.append(None)
            continue
        ax_tuple = (axes,) if isinstance(axes, str) else tuple(axes)
        # skip axes already used by an earlier dim or absent from the mesh
        ax_tuple = tuple(a for a in ax_tuple if a in mesh.shape and a not in used)
        # greedy fallback: drop leading axes until the product divides the
        # dim (e.g. batch 256 on a 512-chip (pod,data,model) DP binding
        # sheds "pod" and shards over (data, model))
        while ax_tuple:
            size = 1
            for a in ax_tuple:
                size *= mesh.shape[a]
            if size > 1 and dim % size == 0:
                break
            ax_tuple = ax_tuple[1:]
        if not ax_tuple:
            parts.append(None)
            continue
        used.update(ax_tuple)
        parts.append(ax_tuple[0] if len(ax_tuple) == 1 else ax_tuple)
    return P(*parts)


def logical_sharding(shape: Sequence[int], *names: Optional[str]) -> Optional[NamedSharding]:
    """NamedSharding for a logical shape, or None when no mesh is active."""
    if len(names) != len(shape):
        raise ValueError(f"{len(names)} names for rank-{len(shape)} shape")
    spec = _spec_for(shape, names)
    if spec is None:
        return None
    return NamedSharding(_STATE.mesh, spec)


def logical(x, *names: Optional[str]):
    """Annotate array ``x`` with logical axis names (no-op without a mesh)."""
    s = logical_sharding(x.shape, *names)
    if s is None:
        return x
    return jax.lax.with_sharding_constraint(x, s)


def data_axis(mesh: Mesh) -> str:
    """The mesh axis carrying data-parallel rows: ``"data"`` when present,
    else the first axis (1-axis ad-hoc meshes in tests/benchmarks)."""
    return "data" if "data" in mesh.shape else mesh.axis_names[0]


def data_axis_size(mesh: Optional[Mesh] = None) -> int:
    """Shard count of the active (or given) mesh's data axis; 1 without a
    mesh — the single-device no-op the sharded decode backend falls back to."""
    mesh = mesh if mesh is not None else _STATE.mesh
    if mesh is None:
        return 1
    return mesh.shape[data_axis(mesh)]


def all_to_all(x, axis: str, split_axis: int = 0, concat_axis: int = 0):
    """Tiled ``all_to_all`` over a named mesh axis (inside ``shard_map``):
    splits ``x``'s ``split_axis`` into one block per shard, sends block *j*
    to shard *j*, and concatenates the received blocks in shard order along
    ``concat_axis`` — the owner-computes exchange primitive (requests out,
    embeddings back)."""
    return jax.lax.all_to_all(x, axis, split_axis, concat_axis, tiled=True)


def data_mesh(n_shards: int, devices: Optional[Sequence] = None) -> Optional[Mesh]:
    """(Re)build the 1-axis ``("data",)`` mesh an N-shard run trains under —
    the mesh-rebuild step of an elastic rescale (``repro.elastic.rescale``)
    and the mesh ``GraphRuntime`` wires at construction.  ``None`` for
    ``n_shards <= 1`` (the single-device paths take the no-mesh branch);
    loud error when the process sees fewer devices than shards, since a
    silent truncation would train a different topology than the spec says."""
    if n_shards <= 1:
        return None
    import numpy as np
    devices = list(devices if devices is not None else jax.devices())
    if len(devices) < n_shards:
        raise ValueError(
            f"n_shards={n_shards} but only {len(devices)} jax devices are "
            f"visible (force host devices via XLA_FLAGS=--xla_force_host_"
            f"platform_device_count=N, see tools/ci.sh --multidevice)")
    return Mesh(np.asarray(devices[:n_shards]), ("data",))


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
