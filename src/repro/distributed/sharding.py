"""Logical-axis sharding rules (MaxText-style) + activation constraint hooks.

Model code annotates tensors with *logical* axis names; the launcher activates
a (mesh, rules) context and the hooks translate logical names to mesh axes.
Outside a context every hook is a no-op, so smoke tests / CPU benches run
unchanged on one device.

Mesh axes (launch/mesh.py):
  * ``pod``   — outer data parallelism across pods (2 pods = 512 chips)
  * ``data``  — FSDP / batch / sequence sharding inside a pod
  * ``model`` — tensor parallelism (heads, ffn, vocab, experts)
"""
from __future__ import annotations

import contextlib
from dataclasses import dataclass, field, replace
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axes = Union[None, str, Tuple[str, ...]]


@dataclass(frozen=True)
class ShardingRules:
    """logical axis name -> tuple of mesh axis names (applied in order)."""
    rules: Dict[str, Tuple[str, ...]] = field(default_factory=dict)

    def mesh_axes(self, logical: Optional[str]) -> Tuple[str, ...]:
        if logical is None:
            return ()
        return self.rules.get(logical, ())

    def override(self, **kw: Tuple[str, ...]) -> "ShardingRules":
        d = dict(self.rules)
        d.update(kw)
        return ShardingRules(d)


DEFAULT_RULES = ShardingRules({
    # activations
    "batch":      ("pod", "data"),
    "seq":        (),                  # seq replicated by default
    "residual_seq": (),                # train cells override to ("model",)
    "kv_seq":     ("pod", "data"),     # long-context decode: KV sequence shard
    "heads":      ("model",),
    "kv_heads":   ("model",),
    "embed":      (),
    "ffn_act":    ("model",),
    "vocab_act":  ("model",),
    # weights: 2-D fsdp x tp
    "fsdp":       ("data",),
    "tensor":     ("model",),
    "expert":     ("model",),
    # graph / recsys
    "edges":      ("pod", "data", "model"),
    "nodes":      ("pod", "data"),
    "table_rows": ("model",),
    "candidates": ("pod", "data", "model"),
})


# --------------------------------------------------------------------- context
_ACTIVE: list = []


@contextlib.contextmanager
def sharding_ctx(mesh: Optional[Mesh], rules: ShardingRules = DEFAULT_RULES):
    """Activate (mesh, rules) for `constrain` hooks inside jit traces."""
    _ACTIVE.append((mesh, rules))
    try:
        yield
    finally:
        _ACTIVE.pop()


def active_mesh() -> Optional[Mesh]:
    if _ACTIVE and _ACTIVE[-1][0] is not None:
        return _ACTIVE[-1][0]
    return None


def _active() -> Tuple[Optional[Mesh], ShardingRules]:
    if _ACTIVE:
        return _ACTIVE[-1]
    return None, DEFAULT_RULES


def logical_spec(logical_axes: Sequence[Optional[str]],
                 shape: Optional[Sequence[int]] = None,
                 mesh: Optional[Mesh] = None,
                 rules: Optional[ShardingRules] = None) -> P:
    """Translate per-dim logical names to a PartitionSpec.

    Mesh axes missing from the mesh are dropped; if ``shape`` is given, axes
    that do not divide the dim are dropped too (robustness for odd configs).
    """
    m, r = _active()
    mesh = mesh or m
    rules = rules or r
    spec = []
    used: set = set()
    for d, name in enumerate(logical_axes):
        axes = []
        size = 1
        for ax in rules.mesh_axes(name):
            if mesh is None or ax not in mesh.shape or ax in used:
                continue
            nsz = size * mesh.shape[ax]
            if shape is not None and shape[d] % nsz != 0:
                continue
            axes.append(ax)
            used.add(ax)
            size = nsz
        spec.append(tuple(axes) if len(axes) > 1 else (axes[0] if axes else None))
    return P(*spec)


def constrain(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """with_sharding_constraint under the active (mesh, rules); no-op otherwise."""
    mesh, rules = _active()
    if mesh is None:
        return x
    assert len(logical_axes) == x.ndim, (logical_axes, x.shape)
    spec = logical_spec(logical_axes, x.shape, mesh, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named_sharding(mesh: Mesh, logical_axes: Sequence[Optional[str]],
                   shape: Optional[Sequence[int]] = None,
                   rules: ShardingRules = DEFAULT_RULES) -> NamedSharding:
    return NamedSharding(mesh, logical_spec(logical_axes, shape, mesh, rules))


__all__ = ["ShardingRules", "DEFAULT_RULES", "sharding_ctx", "constrain",
           "active_mesh", "logical_spec", "named_sharding"]
