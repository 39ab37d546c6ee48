"""Parameter definition trees: one source of truth for shapes, init, and
logical sharding axes.

A ``ParamDef`` records (shape, dtype, logical axes, initializer).  From a tree
of defs we derive (a) initialized arrays, (b) ``jax.ShapeDtypeStruct``s for the
AOT dry-run, and (c) ``PartitionSpec``s by mapping logical axis names through
per-arch sharding rules (MaxText-style; DESIGN.md §5).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


@dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]      # logical axis per dim (None = replicated)
    dtype: Any = jnp.bfloat16
    init: str = "normal"                 # normal | zeros | ones | scaled | a_param
    init_scale: float = 1.0

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


ParamTree = Union[ParamDef, Dict[str, Any], List[Any], Tuple[Any, ...]]


def _is_def(x: Any) -> bool:
    return isinstance(x, ParamDef)


def tree_map_defs(fn: Callable[[ParamDef], Any], tree: ParamTree) -> Any:
    return jax.tree.map(fn, tree, is_leaf=_is_def)


def init_param(key: jax.Array, d: ParamDef) -> jax.Array:
    if d.init == "zeros":
        return jnp.zeros(d.shape, d.dtype)
    if d.init == "ones":
        return jnp.ones(d.shape, d.dtype)
    if d.init == "a_param":
        # RG-LRU decay parameterization: softplus-inv of decays in (0.9, 0.999)
        u = jax.random.uniform(key, d.shape, jnp.float32, 0.9, 0.999)
        return jnp.log(jnp.expm1(-jnp.log(u) * 8.0)).astype(d.dtype)
    fan_in = d.shape[0] if len(d.shape) >= 2 else max(1, d.shape[-1])
    if d.init == "scaled":
        std = d.init_scale / np.sqrt(fan_in)
    else:
        std = 0.02 * d.init_scale
    return (jax.random.normal(key, d.shape, jnp.float32) * std).astype(d.dtype)


def init_params(key: jax.Array, defs: ParamTree) -> Any:
    leaves, treedef = jax.tree.flatten(defs, is_leaf=_is_def)
    keys = jax.random.split(key, len(leaves))
    arrays = [init_param(k, d) for k, d in zip(keys, leaves)]
    return jax.tree.unflatten(treedef, arrays)


def abstract_params(defs: ParamTree) -> Any:
    return tree_map_defs(lambda d: jax.ShapeDtypeStruct(d.shape, d.dtype), defs)


def logical_to_spec(axes: Sequence[Optional[str]],
                    rules: Dict[str, Any],
                    shape: Optional[Sequence[int]] = None,
                    axis_sizes: Optional[Dict[str, int]] = None) -> P:
    """Map logical axis names -> mesh axes via rules; drop collisions and
    (when ``shape``/``axis_sizes`` are given) non-divisible shardings.

    A rule value may be a mesh-axis name, a tuple of mesh axes, or None.
    If two dims would map to the same mesh axis, the later dim wins nothing
    (kept unsharded) — XLA requires each mesh axis used at most once.  Given
    ``axis_sizes``, a rule naming an axis the mesh lacks leaves that dim
    unsharded.
    """
    used: set = set()
    out: List[Any] = []
    for i, ax in enumerate(axes):
        mesh_ax = rules.get(ax) if ax is not None else None
        if mesh_ax is None:
            out.append(None)
            continue
        entries = mesh_ax if isinstance(mesh_ax, tuple) else (mesh_ax,)
        free = tuple(e for e in entries if e not in used
                     and (axis_sizes is None or e in axis_sizes))
        if shape is not None and axis_sizes is not None and free:
            # keep the longest divisible prefix of the mesh-axis tuple
            dim = shape[i]
            kept = []
            prod = 1
            for e in free:
                prod *= axis_sizes[e]
                if dim % prod == 0:
                    kept.append(e)
                else:
                    break
            free = tuple(kept)
        if not free:
            out.append(None)
            continue
        used.update(free)
        out.append(free if len(free) > 1 else free[0])
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def param_specs(defs: ParamTree, rules: Dict[str, Any],
                axis_sizes: Optional[Dict[str, int]] = None) -> Any:
    return tree_map_defs(
        lambda d: logical_to_spec(d.axes, rules, d.shape, axis_sizes), defs)


def param_count(defs: ParamTree) -> int:
    leaves = jax.tree.leaves(defs, is_leaf=_is_def)
    return int(sum(np.prod(d.shape) for d in leaves))


def stack_defs(d: ParamDef, n: int, axis_name: Optional[str] = "layers") -> ParamDef:
    """Add a leading scan (layer-stack) dimension to a def."""
    return ParamDef((n,) + d.shape, (axis_name,) + d.axes, d.dtype, d.init,
                    d.init_scale)


def stack_tree(tree: ParamTree, n: int) -> ParamTree:
    return tree_map_defs(lambda d: stack_defs(d, n, None), tree)
