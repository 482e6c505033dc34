"""Mesh-axis abstraction + sharding-constraint helpers.

The production mesh is (data, model) or (pod, data, model); smoke tests
run on a single device with no mesh. ``constrain`` no-ops when there is
no mesh in context so model code is mesh-agnostic.

Logical sharding rules (DESIGN.md §5):
  batch    -> (pod, data)          activations' leading dim
  seq      -> model                sequence-sharded residual saves (Megatron-SP)
  heads    -> model                q-head / TP dim
  d_ff     -> model                TP dim of MLP hidden
  vocab    -> model                logits TP
  fsdp     -> data                 parameter/optimizer FSDP dim
  experts  -> model (if divisible) EP dim
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
from jax.sharding import PartitionSpec as P


@dataclass(frozen=True)
class MeshAxes:
    dp: tuple[str, ...] = ("data",)  # batch axes (includes 'pod' when present)
    fsdp: str | tuple | None = "data"  # parameter-shard axis (or axes)
    tp: str | None = "model"  # tensor-parallel axis
    dp_size: int = 1  # product of dp axis sizes
    fsdp_size: int = 1
    tp_size: int = 1

    @property
    def all_seq(self) -> tuple[str, ...]:
        """Axes jointly sharding a long KV-cache sequence dim."""
        return tuple(a for a in (*self.dp, self.tp) if a)

    @property
    def all_seq_size(self) -> int:
        return self.dp_size * self.tp_size

    def tp_divides(self, dim: int) -> bool:
        return self.tp is not None and dim % self.tp_size == 0

    def fsdp_divides(self, dim: int) -> bool:
        return self.fsdp is not None and dim % self.fsdp_size == 0

    def fsdp_if(self, dim: int):
        return self.fsdp if self.fsdp_divides(dim) else None

    def tp_if(self, dim: int):
        return self.tp if self.tp_divides(dim) else None


SINGLE = MeshAxes(dp=(), fsdp=None, tp=None)


@dataclass(frozen=True)
class ServePlan:
    """How a decode-shape cell shards its KV cache / recurrent state.

    batch_axes — mesh axes sharding the request batch dim (() when B=1).
    seq_axes   — mesh axes sharding the cache sequence dim; non-empty
                 selects the shard_map flash-combine decode path.
    kv_axes    — tp axis on the KV-head dim (plain GSPMD path), or None.
    """

    batch_axes: tuple[str, ...] = ()
    seq_axes: tuple[str, ...] = ()
    kv_axes: str | None = None


def make_serve_plan(cfg, ax: MeshAxes, batch: int, cache_len: int) -> ServePlan:
    """Pick the decode cache layout for (arch, batch, cache_len).

    Priority: shard KV heads on tp when divisible (cheapest — pure local
    attention); otherwise shard the cache sequence dim on tp; for B == 1
    (long_500k) spread the sequence over every mesh axis.
    """
    if ax.tp is None and not ax.dp:
        return ServePlan()
    batch_axes = ax.dp if (ax.dp and batch % ax.dp_size == 0 and batch >= ax.dp_size) else ()
    kv = getattr(cfg, "num_kv_heads", 0) or 0
    if not batch_axes:
        seq_axes = tuple(a for a in (*ax.dp, ax.tp) if a)
        sz = 1
        for a in seq_axes:
            sz *= ax.dp_size if a in ax.dp else ax.tp_size
        if cache_len and cache_len % max(sz, 1) == 0:
            return ServePlan(batch_axes=(), seq_axes=seq_axes, kv_axes=None)
        return ServePlan()
    if ax.tp and kv and kv % ax.tp_size == 0:
        return ServePlan(batch_axes=batch_axes, seq_axes=(), kv_axes=ax.tp)
    if ax.tp and cache_len and cache_len % ax.tp_size == 0:
        return ServePlan(batch_axes=batch_axes, seq_axes=(ax.tp,), kv_axes=None)
    return ServePlan(batch_axes=batch_axes)


def axes_for_mesh(mesh, strategy: str = "2d") -> MeshAxes:
    """strategy:
      "2d"   — batch on (pod, data); params FSDP on data, TP on model
               (Megatron x ZeRO; the default and the decode/prefill mode).
      "fsdp" — no tensor parallelism: batch on (pod, data, model) when it
               divides, params FSDP over (data, model). Eliminates all
               per-layer activation collectives in exchange for per-layer
               parameter all-gathers (§Perf iteration A).
      "tp_only" — serving mode: params replicated over data, TP over
               model. Decode steps stop paying per-layer FSDP weight
               gathers (28 MB/layer) for tiny activation ARs
               (§Perf iteration E); requires params_bf16/tp to fit HBM."""
    names = mesh.axis_names
    shape = dict(zip(mesh.axis_names, mesh.devices.shape)) if hasattr(mesh, "devices") else dict(mesh.shape)
    if strategy == "tp_only":
        dp = tuple(a for a in ("pod", "data") if a in names)
        dp_size = 1
        for a in dp:
            dp_size *= shape[a]
        return MeshAxes(dp=dp, fsdp=None, tp="model" if "model" in names else None,
                        dp_size=dp_size, fsdp_size=1, tp_size=shape.get("model", 1))
    if strategy == "fsdp":
        fsdp_axes = tuple(a for a in ("data", "model") if a in names)
        fsdp_size = 1
        for a in fsdp_axes:
            fsdp_size *= shape[a]
        dp = tuple(a for a in ("pod", *fsdp_axes) if a in names)
        dp_size = 1
        for a in dp:
            dp_size *= shape[a]
        return MeshAxes(dp=dp, fsdp=fsdp_axes, tp=None, dp_size=dp_size,
                        fsdp_size=fsdp_size, tp_size=1)
    dp = tuple(a for a in ("pod", "data") if a in names)
    dp_size = 1
    for a in dp:
        dp_size *= shape[a]
    return MeshAxes(
        dp=dp,
        fsdp="data" if "data" in names else None,
        tp="model" if "model" in names else None,
        dp_size=dp_size,
        fsdp_size=shape.get("data", 1),
        tp_size=shape.get("model", 1),
    )


def has_mesh() -> bool:
    return not jax.sharding.get_abstract_mesh().empty


def constrain(x, spec: P):
    if not has_mesh():
        return x
    return jax.lax.with_sharding_constraint(x, spec)
