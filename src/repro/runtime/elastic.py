"""Elastic re-meshing: resume the same job on a different node count.

Paper requirement 4 ("the application can be built and deployed ... using
different workstations, not restricted to a specific set") maps to: rebuild
the mesh from the surviving devices, re-derive every sharding through the
same rules, restore the checkpoint against the new shardings, continue.
``Nclusters`` is a *parameter* of the deployment, exactly as in the DSL.

SPMD cannot change topology mid-step, so elasticity is a step-boundary
operation: detect -> checkpoint (or use the last async one) -> rebuild ->
restore -> resume.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

from repro.core.channels import ShardingRules, rules_for_shape_kind


@dataclass
class ElasticController:
    """Owns the device pool and builds (mesh, rules) for a node count."""

    model_axis: int = 1
    devices_per_node: int = 1
    shape_kind: str = "train"

    def available_nodes(self, excluded: set[int] | None = None) -> list[int]:
        n_dev = len(jax.devices())
        nodes = n_dev // (self.devices_per_node * self.model_axis)
        return [n for n in range(nodes) if n not in (excluded or set())]

    def build(self, nodes: list[int]) -> tuple[Mesh, ShardingRules]:
        if not nodes:
            raise RuntimeError("no surviving nodes to build a mesh from")
        per_node = self.devices_per_node * self.model_axis
        devs = np.asarray(jax.devices())
        chosen = np.concatenate(
            [devs[n * per_node : (n + 1) * per_node] for n in nodes]
        )
        data = len(nodes) * self.devices_per_node
        mesh_devs = chosen.reshape(data, self.model_axis)
        mesh = Mesh(mesh_devs, ("data", "model"),
                    axis_types=(AxisType.Auto,) * 2)
        rules = rules_for_shape_kind(mesh, self.shape_kind)
        return mesh, rules

    def largest_batch_divisor_nodes(self, global_batch: int,
                                    excluded: set[int]) -> list[int]:
        """Pick the largest surviving node subset whose data-parallel degree
        divides the global batch (keeps the step semantics identical)."""
        nodes = self.available_nodes(excluded)
        while nodes:
            data = len(nodes) * self.devices_per_node
            if global_batch % data == 0:
                return nodes
            nodes = nodes[:-1]
        raise RuntimeError("no node subset divides the global batch")
