"""Divisibility-aware logical sharding rules.

Model code annotates tensors with *logical* dimension names; this module
resolves them to ``PartitionSpec``s against whatever mesh is active.  A rule
is applied only when the dimension size divides the product of the mapped
mesh axes — otherwise that dimension is left unsharded.  This single policy
makes every assigned architecture shard cleanly on the production meshes:

* qwen2-7b has 28 query heads (not divisible by model=16) -> heads stay
  replicated over TP while d_ff / vocab still shard (the §Perf hillclimb
  measures what that costs and fixes it with head padding);
* GQA kv heads (4, 5, 8) < 16 -> kv tensors replicate over TP, the standard
  GQA tensor-parallel fallback;
* long_500k has batch=1 -> batch rules no-op and the KV cache shards its
  *sequence* axis instead (context parallelism), see ``cache_pspec``.

The active mesh comes from ``use_mesh`` (a contextvar), so reduced-config
smoke tests on one CPU device run the exact same model code with every
constraint collapsing to a no-op.
"""

from __future__ import annotations

import contextlib
import contextvars
import math
from typing import Any, Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# The serving-path tensor axis (sharded pods).  Distinct from the
# training axis "model" on purpose: rules that would split a contraction
# (d_ff, vocab, row-parallel "tp") deliberately do NOT map to it, so a
# serving mesh only ever moves data with exact collectives (all-gather /
# masked gather) and, with float32 weights, a sharded pod's token streams
# stay bit-identical to the single-device reference (bf16: see
# serve_pspec).
SERVE_AXIS = "serve"

# Logical dimension name -> preferred mesh axes (in order).
RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "seq": (),  # unsharded by default (sequence parallelism is opt-in)
    "seq_shard": ("pod", "data"),  # context-parallel sequence (long decode)
    "d_model": (),  # activations keep d_model local
    "heads": ("model", SERVE_AXIS),
    "kv_heads": ("model", SERVE_AXIS),
    "d_ff": ("model",),
    "vocab": ("model",),
    "fsdp": ("data",),  # parameter d_model/d_ff dims shard over data (FSDP)
    "experts": ("model",),
    "layers": (),  # stacked-layer leading dim of scanned params
    "state": (),
    None: (),
}

_mesh_var: contextvars.ContextVar[Optional[Mesh]] = contextvars.ContextVar(
    "repro_mesh", default=None)


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh]):
    token = _mesh_var.set(mesh)
    try:
        yield mesh
    finally:
        _mesh_var.reset(token)


def current_mesh() -> Optional[Mesh]:
    return _mesh_var.get()


def _axes_in_mesh(mesh: Mesh, axes: Sequence[str]) -> tuple[str, ...]:
    return tuple(a for a in axes if a in mesh.shape)


def resolve_pspec(names: Sequence[Optional[str]], shape: Sequence[int],
                  mesh: Mesh) -> P:
    """Logical names -> PartitionSpec, dropping non-divisible rules."""
    if len(names) != len(shape):
        raise ValueError(f"rank mismatch: {names} vs shape {shape}")
    spec: list[Any] = []
    used: set[str] = set()
    for name, dim in zip(names, shape):
        axes = _axes_in_mesh(mesh, RULES.get(name, ()))
        axes = tuple(a for a in axes if a not in used)
        # Largest prefix of the preferred axes that divides the dim.
        while axes and dim % math.prod(mesh.shape[a] for a in axes) != 0:
            axes = axes[:-1]
        if axes:
            used.update(axes)
            spec.append(axes if len(axes) > 1 else axes[0])
        else:
            spec.append(None)
    return P(*spec)


def named(x: jax.Array | Any, *names: Optional[str]) -> jax.Array:
    """Annotate ``x`` with logical dimension names (no-op without a mesh)."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = resolve_pspec(names, x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ``constrain`` is the verb used inside model code.
constrain = named


def cache_pspec(shape: Sequence[int], mesh: Mesh,
                layout: Sequence[Optional[str]] = ("layers", "batch", "seq",
                                                   "kv_heads", None)) -> P:
    """KV-cache spec: every mesh axis must shard *something* or the cache
    replicates and overflows HBM (e.g. qwen1.5-110b decode_32k is 1.4 TB).

    Assignment policy:
      * batch takes (pod, data) when divisible; otherwise those axes move
        to seq (context parallelism — long_500k, batch=1);
      * kv_heads takes model when divisible (GQA with kv >= TP); otherwise
        model also moves to seq (kv=4/5/8 archs), giving flash-decoding
        style sequence-sharded attention with a softmax combine.
    """
    names = list(layout)
    if "batch" not in names or "seq" not in names:
        return resolve_pspec(names, shape, mesh)
    b_idx, s_idx = names.index("batch"), names.index("seq")
    seq_axes: list[str] = []
    dp_axes = _axes_in_mesh(mesh, RULES["batch"])
    dp = math.prod(mesh.shape[a] for a in dp_axes)
    if dp and shape[b_idx] % dp != 0:
        names[b_idx] = None
        seq_axes.extend(dp_axes)
    if "kv_heads" in names:
        k_idx = names.index("kv_heads")
        tp_axes = _axes_in_mesh(mesh, RULES["kv_heads"])
        tp = math.prod(mesh.shape[a] for a in tp_axes)
        if tp and shape[k_idx] % tp != 0:
            names[k_idx] = None
            seq_axes.extend(tp_axes)
    if seq_axes:
        total = math.prod(mesh.shape[a] for a in seq_axes)
        if shape[s_idx] % total == 0:
            spec = resolve_pspec(names, shape, mesh)
            parts = list(spec)
            parts[s_idx] = tuple(seq_axes) if len(seq_axes) > 1 else seq_axes[0]
            return P(*parts)
    return resolve_pspec(names, shape, mesh)


def tp_mesh(shards: int,
            devices: Optional[Sequence[Any]] = None) -> Optional[Mesh]:
    """Single-axis ``(SERVE_AXIS,)`` tensor-parallel mesh over ``shards``
    devices — the mesh a multi-rectangle FaSTPod runs under.

    ``shards == 1`` returns ``None`` — the caller's single-device path must
    stay byte-identical to today's, so no mesh object exists to thread.
    ``devices`` selects the member devices explicitly (a sharded pod's
    rectangles name their own nodes); default is the first ``shards`` of
    ``jax.devices()``.
    """
    if shards < 1:
        raise ValueError(f"shards must be >= 1, got {shards}")
    if shards == 1:
        return None
    devs = list(devices) if devices is not None else jax.devices()
    if len(devs) < shards:
        raise ValueError(
            f"need {shards} devices for a tp mesh, have {len(devs)}")
    import numpy as np
    return Mesh(np.asarray(devs[:shards]), (SERVE_AXIS,))


def serve_tp(mesh: Optional[Mesh] = None) -> int:
    """Size of the serving tensor axis in ``mesh`` (or the active mesh);
    1 when absent — i.e. on every training/single-device path."""
    mesh = mesh if mesh is not None else current_mesh()
    if mesh is None:
        return 1
    return int(mesh.shape.get(SERVE_AXIS, 1))


def serve_pspec(names: Sequence[Optional[str]], shape: Sequence[int],
                mesh: Mesh) -> P:
    """Column-only tensor-parallel placement for serving-path parameters.

    Shards a parameter's OUTPUT dimensions — a trailing ``"tp"`` (column-
    parallel projections and their biases) or any ``"vocab"`` dim — over
    ``SERVE_AXIS`` and replicates everything else, in particular the
    row-parallel ``"tp"`` dims of wo / w_down.  With contracting rows
    replicated, every dot runs its full reduction on-device and the only
    cross-device exchanges are exact (all-gathers, masked embedding
    gathers), so with float32 weights a sharded pod's logits are bitwise
    those of the single-device reference — the reassociation of a
    split-K all-reduce would not be.  With bf16 weights the partitioned
    program's codegen alone moves logits by about bf16 rounding
    (distributed/README.md).  Non-divisible dims stay
    replicated (the usual divisibility fallback).
    """
    if len(names) != len(shape):
        raise ValueError(f"rank mismatch: {names} vs shape {shape}")
    n = mesh.shape.get(SERVE_AXIS, 0)
    spec: list[Any] = []
    for i, (name, dim) in enumerate(zip(names, shape)):
        col = name == "vocab" or (name == "tp" and i == len(names) - 1)
        spec.append(SERVE_AXIS if (col and n > 1 and dim % n == 0)
                    else None)
    return P(*spec)


def _is_name_tuple(x: Any) -> bool:
    return isinstance(x, tuple) and all(
        isinstance(i, (str, type(None))) for i in x)


def shard_put(tree: Any, names_tree: Any, mesh: Mesh,
              resolver=resolve_pspec) -> Any:
    """``device_put`` every leaf of ``tree`` to its resolved NamedSharding.

    ``names_tree`` mirrors ``tree`` with logical-name tuples at the leaves
    (``Model.param_names()`` / ``Model.cache_names()`` shape); ``resolver``
    maps ``(names, shape, mesh)`` to a PartitionSpec (``serve_pspec`` for
    serving-path parameters).  Re-placing an already-correctly-sharded
    leaf is a no-op, so this is safe to call on the output of a sharded
    upload.
    """
    return jax.tree_util.tree_map(
        lambda names, leaf: jax.device_put(
            leaf, NamedSharding(mesh, resolver(names, leaf.shape, mesh))),
        names_tree, tree, is_leaf=_is_name_tuple)


def sharding_for(names: Sequence[Optional[str]], shape: Sequence[int],
                 mesh: Optional[Mesh] = None) -> Optional[NamedSharding]:
    mesh = mesh or current_mesh()
    if mesh is None:
        return None
    return NamedSharding(mesh, resolve_pspec(names, shape, mesh))


def tree_shardings(spec_tree: Any, shape_tree: Any, mesh: Mesh,
                   resolver=resolve_pspec) -> Any:
    """Map a pytree of logical-name tuples + matching ShapeDtypeStructs to
    NamedShardings (jit in/out shardings: the dry-run, or weights made
    straight into a serving pod's placement with ``serve_pspec``)."""
    return jax.tree_util.tree_map(
        lambda names, sds: NamedSharding(
            mesh, resolver(names, sds.shape, mesh)),
        spec_tree, shape_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(i, (str, type(None))) for i in x),
    )
