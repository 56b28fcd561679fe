"""Execution context threaded through model apply functions.

Carries the mesh + axis names so modules that need explicit SPMD (the
expert-parallel MoE shard_map) can use them, plus the attention impl switch.
``ExecContext()`` (no mesh) is the single-device path used by CPU tests.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Tuple


@dataclass(frozen=True)
class ExecContext:
    mesh: object = None  # jax.sharding.Mesh | None
    batch_axes: Tuple[str, ...] = ()  # mesh axes sharding the batch dim
    model_axis: Optional[str] = None  # mesh axis sharding heads/ffn/experts
    attn_impl: str = "xla"  # "xla" | "pallas"
    # per-run layout switches the model code reads ("moe_2d",
    # "attn_seq_shard", "remat_policy", "pipeline"); set by the caller,
    # e.g. launch/dryrun.py's --plan
    plan: dict = field(default_factory=dict)

    @property
    def model_parallel(self) -> int:
        if self.mesh is None or self.model_axis is None:
            return 1
        return self.mesh.shape[self.model_axis]

    @property
    def batch_parallel(self) -> int:
        if self.mesh is None:
            return 1
        n = 1
        for a in self.batch_axes:
            n *= self.mesh.shape[a]
        return n
