"""Config registry plumbing: the APSS workload registers an ``ArchDef``
whose shape cells build ``(fn, args, in_shardings, out_shardings)``
lowering specs for the dry-run (DESIGN.md §4).

Builders return abstract ``jax.ShapeDtypeStruct`` argument trees — no host
allocation ever happens for the full configs (they are exercised ONLY via
``launch/dryrun.py``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _filter_spec(mesh: Mesh, spec: P) -> P:
    def keep(part):
        if part is None:
            return None
        if isinstance(part, (tuple, list)):
            kept = tuple(a for a in part if a in mesh.shape)
            return kept if kept else None
        return part if part in mesh.shape else None

    return P(*(keep(part) for part in spec))


def shardings_for(mesh: Mesh, specs):
    """Pytree of PartitionSpec → pytree of NamedSharding (mesh-filtered)."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, _filter_spec(mesh, s)),
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def sds(shape, dtype) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


@dataclasses.dataclass(frozen=True)
class CellBuild:
    """Everything the dry-run needs to lower one (arch × shape × mesh)."""

    fn: Callable
    args: tuple                 # ShapeDtypeStruct pytrees
    in_shardings: tuple
    out_shardings: Any          # pytree or None (auto)
    static_info: dict           # model flops etc. for the roofline


@dataclasses.dataclass(frozen=True)
class ShapeCell:
    kind: str                   # apss
    desc: str
    build: Callable[[Any, Mesh], CellBuild]   # (full_config, mesh) -> CellBuild


@dataclasses.dataclass(frozen=True)
class ArchDef:
    name: str
    source: str                 # public-literature citation tag
    make_config: Callable[[], Any]
    shapes: dict

    def cell(self, shape: str) -> ShapeCell:
        return self.shapes[shape]


REGISTRY: dict = {}


def register(arch: ArchDef) -> ArchDef:
    REGISTRY[arch.name] = arch
    return arch


def get_arch(name: str) -> ArchDef:
    if name not in REGISTRY:
        # Import side effects populate the registry lazily.
        import repro.configs  # noqa: F401
    return REGISTRY[name]
