"""Mesh axes & sharding rules (DESIGN.md §5).

Production mesh: ("data","model") single pod, ("pod","data","model") multi
pod. Batch shards over (pod, data); attention heads / MLP hidden / vocab
over model; MoE experts over model — or (data, model) for expert counts
that need 2-D sharding (deepseek-v3, 256 experts -> 1/device).

``Axes`` is threaded through the model; ``axes=None`` (single-device smoke
tests) turns every constraint into a no-op, so the same model code runs
unsharded on CPU and 512-way on the production mesh.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class Axes:
    batch: tuple[str, ...] = ("data",)  # ("pod","data") multi-pod
    model: str = "model"
    expert: tuple[str, ...] = ("model",)  # ("data","model") for 2-D EP
    mesh_shape: dict | None = None  # axis name -> size
    mesh: object = None  # the jax Mesh (for shard_map islands)

    @property
    def model_size(self) -> int:
        return self.mesh_shape[self.model] if self.mesh_shape else 1

    @property
    def expert_size(self) -> int:
        if not self.mesh_shape:
            return 1
        s = 1
        for a in self.expert:
            s *= self.mesh_shape[a]
        return s

    def pad_heads(self, h: int) -> int:
        m = self.model_size
        return ((h + m - 1) // m) * m

    def kv_spec(self, kv_heads: int):
        """Shard KV heads on model only when divisible; else replicate."""
        m = self.model_size
        return self.model if (kv_heads % m == 0 and kv_heads >= m) else None


def from_mesh(mesh: jax.sharding.Mesh | None, expert_2d: bool = False) -> Axes | None:
    if mesh is None:
        return None
    names = mesh.axis_names
    batch = tuple(a for a in ("pod", "data") if a in names)
    expert = ("data", "model") if expert_2d else ("model",)
    return Axes(
        batch=batch,
        model="model",
        expert=expert,
        mesh_shape={a: int(s) for a, s in zip(names, mesh.devices.shape)},
        mesh=mesh,
    )


def constrain(x: jnp.ndarray, axes: Axes | None, *spec_dims) -> jnp.ndarray:
    """with_sharding_constraint if a mesh is active, else identity.

    spec_dims entries: None | axis-name | tuple of axis names | "batch"
    (expands to the batch axis tuple) | "expert" (expert axes tuple).
    """
    if axes is None:
        return x
    dims = []
    for d in spec_dims:
        if d == "batch":
            dims.append(axes.batch)
        elif d == "expert":
            dims.append(axes.expert)
        else:
            dims.append(d)
    return jax.lax.with_sharding_constraint(x, P(*dims))


def vocab_pad(vocab: int, axes: Axes | None, multiple: int = 128) -> int:
    m = axes.model_size if axes else 1
    step = max(multiple, m)
    return ((vocab + step - 1) // step) * step
