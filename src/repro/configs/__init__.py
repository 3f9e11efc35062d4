"""Architecture registry: every assigned arch (+ the paper's own DLRM) as a
selectable config exposing dry-run cells and a reduced smoke test.

Interface:
  get(arch_id) -> ArchDef
  ArchDef.build_cell(shape, mesh, multi_pod) -> CellBuild  (abstract, no alloc)
  ArchDef.smoke() -> dict of metrics  (tiny config, real compute on CPU)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

REGISTRY: dict[str, "ArchDef"] = {}


@dataclasses.dataclass
class CellBuild:
    """Everything needed to lower one (arch x shape x mesh) dry-run cell."""

    step_name: str
    step_fn: Callable
    args: tuple  # tree of jax.ShapeDtypeStruct
    in_shardings: tuple  # tree of PartitionSpec, matching args
    donate_argnums: tuple[int, ...] = ()
    static_argnums: tuple[int, ...] = ()


@dataclasses.dataclass
class ArchDef:
    id: str
    kind: str  # 'lm-dense' | 'lm-moe' | 'recsys' | 'gnn'
    shapes: tuple[str, ...]
    build_cell: Callable[[str, Any, bool], CellBuild]
    smoke: Callable[[], dict]
    notes: str = ""


def register(arch: ArchDef) -> ArchDef:
    REGISTRY[arch.id] = arch
    return arch


def get(arch_id: str) -> ArchDef:
    if arch_id not in REGISTRY:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(REGISTRY)}")
    return REGISTRY[arch_id]


def list_archs() -> list[str]:
    return sorted(REGISTRY)


def input_specs(arch_id: str, shape: str, mesh=None, multi_pod: bool = False):
    """ShapeDtypeStruct stand-ins for every input of the (arch x shape) step
    (weak-type-correct, shardable, no device allocation).  `mesh` defaults to
    an AbstractMesh of the production 16x16 pod."""
    if mesh is None:
        from repro.launch.mesh import abstract_mesh

        shape_ax = ((2, 16, 16), ("pod", "data", "model")) if multi_pod else (
            (16, 16), ("data", "model"))
        mesh = abstract_mesh(*shape_ax)
    build = get(arch_id).build_cell(shape, mesh, multi_pod)
    return build.args


ASSIGNED = [
    "stablelm-3b",
    "llama3-405b",
    "qwen2-72b",
    "arctic-480b",
    "olmoe-1b-7b",
    "graphsage-reddit",
    "mind",
    "autoint",
    "wide-deep",
    "two-tower-retrieval",
]

# Populate the registry (assigned archs + the paper's DLRM + extras).
from repro.configs import (  # noqa: E402,F401
    arctic_480b,
    autoint,
    dcn_v2,
    deepfm,
    dlrm_flexemr,
    graphsage_reddit,
    llama3_405b,
    mind,
    olmoe_1b_7b,
    qwen2_72b,
    stablelm_3b,
    two_tower_retrieval,
    wide_deep,
)
