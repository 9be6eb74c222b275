"""Logical-axis sharding rules (MaxText-style) for the whole framework.

Models annotate tensors with *logical* axis names; a rule set maps logical
names to physical mesh axes.  The same model code then runs on the
single-pod (16,16) "data"/"model" mesh, the multi-pod (2,16,16) mesh, a
tiny test mesh, or one device (rules absent → constraint is a no-op).

Parallelism coverage:
  DP    : "batch"   → ("pod","data")   (pod axis = cross-pod data parallel)
  FSDP  : "fsdp"    → "data"           (param/optimizer-state sharding)
  TP    : "heads"/"mlp"/"vocab" → "model"
  EP    : "experts" → "model"          (MoE expert parallelism)
  SP    : "seq"     → "model"          (sequence sharding for long prefill,
                                        enabled per-config)
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

Rules = Tuple[Tuple[str, object], ...]


# default rule set for the production meshes (see launch/mesh.py)
DEFAULT_RULES: Rules = (
    ("batch", ("pod", "data")),
    ("seq", None),           # overridden to "model" when SP is on
    ("embed", None),
    ("heads", "model"),
    ("kv_heads", "model"),
    ("head_dim", None),
    ("mlp", "model"),
    ("vocab", "model"),
    ("experts", "model"),
    ("expert_mlp", None),
    ("fsdp", "data"),        # parameter / optimizer-state sharding axis
    ("layers", None),
    ("state", None),         # SSM state / conv / lru lanes
    ("kv_seq", None),
)

_ctx = threading.local()


def _current() -> tuple[Optional[Mesh], Rules]:
    return getattr(_ctx, "mesh", None), getattr(_ctx, "rules", DEFAULT_RULES)


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh], rules: Rules = DEFAULT_RULES):
    """Activate (mesh, rules) for logical constraints within the block."""
    old = _current()
    _ctx.mesh, _ctx.rules = mesh, rules
    try:
        yield
    finally:
        _ctx.mesh, _ctx.rules = old


def current_mesh() -> Optional[Mesh]:
    return _current()[0]


def current_rules() -> Rules:
    return _current()[1]


def physical_axes(name: str, shape_dim: Optional[int] = None):
    """Mesh axes a logical name maps to (divisibility-filtered prefix)."""
    mesh, rules = _current()
    if mesh is None:
        return ()
    rd = dict(rules)
    phys = rd.get(name)
    if phys is None:
        return ()
    if isinstance(phys, str):
        phys = (phys,)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    keep = []
    prod = 1
    for p in phys:
        if p not in sizes:
            continue
        if shape_dim is not None and shape_dim % (prod * sizes[p]) != 0:
            break
        keep.append(p)
        prod *= sizes[p]
    return tuple(keep)


def with_rules(overrides: dict) -> Rules:
    base = dict(DEFAULT_RULES)
    base.update(overrides)
    return tuple(base.items())


def logical_to_spec(logical: Sequence[Optional[str]],
                    rules: Rules = None,
                    mesh: Optional[Mesh] = None,
                    shape: Optional[Sequence[int]] = None) -> PartitionSpec:
    """Map logical axis names to a PartitionSpec under the active rules.

    Axes whose physical target is absent from the mesh are left unsharded —
    the same config lowers on any mesh (e.g. no "pod" axis single-pod).
    Physical axes already used by an earlier dim are dropped (first wins).
    If ``shape`` is given, mesh axes that do not divide the dim are dropped
    (longest dividing prefix wins) — 24 heads on a 16-way model axis, MQA
    kv=1 caches, batch=1 decode etc. degrade to replication instead of
    failing to lower.
    """
    if rules is None:
        _, rules = _current()
    if mesh is None:
        mesh, _ = _current()
    rd = dict(rules)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape)) if mesh is not None \
        else {}
    used = set()
    out = []
    for i, name in enumerate(logical):
        phys = rd.get(name) if name is not None else None
        if phys is None:
            out.append(None)
            continue
        if isinstance(phys, str):
            phys = (phys,)
        keep = [p for p in phys if p in sizes and p not in used]
        if shape is not None and i < len(shape):
            # longest prefix of axes whose product divides the dim
            prefix = []
            prod = 1
            for p in keep:
                if shape[i] % (prod * sizes[p]) == 0:
                    prefix.append(p)
                    prod *= sizes[p]
                else:
                    break
            keep = prefix
        used.update(keep)
        if not keep:
            out.append(None)
        elif len(keep) == 1:
            out.append(keep[0])
        else:
            out.append(tuple(keep))
    return PartitionSpec(*out)


def constrain(x, *logical: Optional[str]):
    """with_sharding_constraint by logical names; no-op without a mesh."""
    mesh, rules = _current()
    if mesh is None:
        return x
    spec = logical_to_spec(logical, rules, mesh, shape=x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named_sharding(logical: Sequence[Optional[str]],
                   mesh: Optional[Mesh] = None,
                   shape: Optional[Sequence[int]] = None) -> NamedSharding:
    m, rules = _current()
    mesh = mesh or m
    assert mesh is not None, "named_sharding needs an active or explicit mesh"
    return NamedSharding(mesh, logical_to_spec(logical, rules, mesh, shape))


def tree_shardings(logical_tree, mesh: Mesh, rules: Rules = DEFAULT_RULES,
                   shape_tree=None):
    """Map a pytree of logical-axis tuples to NamedShardings.  With
    ``shape_tree`` (matching abstract arrays), indivisible axes are
    dropped per-leaf."""
    is_lg = lambda x: isinstance(x, tuple)
    if shape_tree is None:
        return jax.tree.map(
            lambda lg: NamedSharding(mesh, logical_to_spec(lg, rules, mesh)),
            logical_tree, is_leaf=is_lg)
    flat_lg, tdef = jax.tree_util.tree_flatten(logical_tree, is_leaf=is_lg)
    flat_sh = tdef.flatten_up_to(shape_tree)
    out = [NamedSharding(mesh, logical_to_spec(lg, rules, mesh,
                                               getattr(s, "shape", None)))
           for lg, s in zip(flat_lg, flat_sh)]
    return tdef.unflatten(out)
