"""Logical-axis sharding rules: DP / FSDP / TP / EP / SP on one mesh.

Every parameter and state tensor carries logical axis names
(``repro.models.layers.ParamDef.axes``); this module maps them onto mesh
axes under a :class:`ShardingPolicy`, with divisibility fallbacks so the
same model re-derives valid shardings on any mesh shape (elastic restarts,
DESIGN.md §6).

Axis policy (defaults):
    vocab/heads/kv_heads/mlp/experts/inner  → "model"   (TP / EP)
    embed                                   → dp axes when FSDP (ZeRO-3)
    batch                                   → ("pod","data")
    kv_len / seq                            → "model" only as fallback when
                                              the head axis can't use it (SP)
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro import models


@dataclasses.dataclass(frozen=True)
class ShardingPolicy:
    #: mesh data-parallel axes (in spec order), e.g. ("pod", "data")
    dp_axes: Tuple[str, ...] = ("data",)
    #: mesh tensor-parallel axis
    tp_axis: str = "model"
    #: mesh pipeline-stage axis (stacked layer dim of params + KV pool)
    pp_axis: str = "pipe"
    #: shard params' "embed" axis over dp (ZeRO-3 / FSDP)
    fsdp: bool = False
    #: shard sequence over tp for activations when batch < dp (long context)
    seq_shard: bool = False

    def primary_rules(self) -> Dict[str, Sequence]:
        tp = (self.tp_axis,)
        rules: Dict[str, Sequence] = {
            "vocab": tp, "heads": tp, "kv_heads": tp, "mlp": tp,
            "experts": tp, "inner": tp,
            "batch": (self.dp_axes,),    # tuple-of-axes = combined sharding
            "layers": (self.pp_axis,),   # stacked layer dim → pipeline stage
        }
        if self.fsdp:
            rules["embed"] = (self.dp_axes,)
        if self.seq_shard:
            rules["seq"] = tp
        return rules

    def fallback_rules(self) -> Dict[str, Sequence]:
        # used only if the primary owner of the tp axis was not divisible;
        # KV length always falls back (cache memory dominates decode);
        # activation seq only under an explicit sequence-sharding policy
        rules: Dict[str, Sequence] = {"kv_len": (self.tp_axis,)}
        if self.seq_shard:
            rules["seq"] = (self.tp_axis,)
        return rules


def tp_degree(mesh: Mesh, policy: ShardingPolicy) -> int:
    """Tensor-parallel ways of this mesh under the policy (1 if the mesh
    has no tp axis) — the engine's measured counterpart of
    ``repro.core.ShardingPlan.tp``."""
    return int(mesh.shape.get(policy.tp_axis, 1))


def pp_degree(mesh: Mesh, policy: ShardingPolicy) -> int:
    """Pipeline-parallel ways of this mesh under the policy (1 if the
    mesh has no pipe axis) — the measured counterpart of
    ``repro.core.ShardingPlan.pp``."""
    return int(mesh.shape.get(policy.pp_axis, 1))


def _axis_size(mesh: Mesh, axis) -> int:
    if isinstance(axis, tuple):
        n = 1
        for a in axis:
            n *= mesh.shape[a]
        return n
    return mesh.shape[axis]


def spec_for(axes: Tuple[Optional[str], ...], shape: Tuple[int, ...],
             mesh: Mesh, policy: ShardingPolicy) -> P:
    """Resolve one tensor's logical axes to a PartitionSpec.

    Two passes: primary rules first (TP/DP/FSDP owners), then fallbacks
    (sequence sharding) for mesh axes still unused.  Any assignment failing
    divisibility is dropped (replicated) — never an error.
    """
    assert len(axes) == len(shape), (axes, shape)
    used = set()
    out: list = [None] * len(axes)
    for rules in (ShardingPolicy.primary_rules(policy),
                  ShardingPolicy.fallback_rules(policy)):
        for i, name in enumerate(axes):
            if out[i] is not None or name is None or name not in rules:
                continue
            for cand in rules[name]:
                flat = cand if isinstance(cand, tuple) else (cand,)
                if any(a in used for a in flat):
                    continue
                if any(a not in mesh.shape for a in flat):
                    continue
                if shape[i] % _axis_size(mesh, cand) != 0:
                    continue
                out[i] = cand
                used.update(flat)
                break
    return P(*out)


def _tree_specs(axes_tree, shape_tree, mesh, policy):
    return jax.tree_util.tree_map(
        lambda ax, sds: NamedSharding(
            mesh, spec_for(tuple(ax), tuple(sds.shape), mesh, policy)),
        axes_tree, shape_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))


def param_shardings(cfg: ArchConfig, mesh: Mesh, policy: ShardingPolicy):
    """NamedSharding tree matching ``models.abstract_params(cfg)``."""
    return _tree_specs(models.logical_axes(cfg),
                       models.abstract_params(cfg), mesh, policy)


# ---------------------------------------------------------------------------
# decode-state logical axes (mirrors models.init_decode_state structure)
# ---------------------------------------------------------------------------

def decode_state_axes(cfg: ArchConfig) -> Dict:
    axes: Dict = {"pos": ()}
    kinds = cfg.block_kinds()
    if any(k == "attn" for k in kinds):
        if cfg.mla is not None:
            axes["cache_k"] = ("layers", "batch", "kv_len", None)
            axes["cache_v"] = ("layers", "batch", "kv_len", None)
        else:
            axes["cache_k"] = ("layers", "batch", "kv_len", "kv_heads", None)
            axes["cache_v"] = ("layers", "batch", "kv_len", "kv_heads", None)
        if cfg.local_window:
            axes["cache_pos"] = ("layers", "batch", "kv_len")
    if any(k == "ssm" for k in kinds):
        axes["conv_state"] = ("layers", "batch", None, "inner")
        axes["ssm_state"] = ("layers", "batch", "inner", None)
    if any(k == "rglru" for k in kinds):
        axes["rg_conv"] = ("layers", "batch", None, "inner")
        axes["rg_h"] = ("layers", "batch", "inner")
    if cfg.family == "encdec":
        axes["cross_k"] = ("layers", "batch", None, "kv_heads", None)
        axes["cross_v"] = ("layers", "batch", None, "kv_heads", None)
    return axes


def decode_state_shardings(cfg: ArchConfig, batch: int, max_len: int,
                           mesh: Mesh, policy: ShardingPolicy):
    shapes = models.abstract_decode_state(cfg, batch, max_len)
    axes = decode_state_axes(cfg)
    out = {}
    for k, sds in shapes.items():
        out[k] = NamedSharding(
            mesh, spec_for(tuple(axes[k]), tuple(sds.shape), mesh, policy))
    return out


def batch_shardings(cfg: ArchConfig, mesh: Mesh, policy: ShardingPolicy,
                    batch_struct: Dict):
    """Shardings for a data batch dict (inputs/targets/mask/frames/...)."""
    field_axes = {
        "inputs": ("batch", "seq"), "targets": ("batch", "seq"),
        "mask": ("batch", "seq"), "tokens": ("batch", "seq"),
        "frames": ("batch", None, None),
        "vision_embeds": ("batch", None, None),
    }
    out = {}
    for k, sds in batch_struct.items():
        ax = field_axes.get(k, tuple(["batch"] + [None] * (len(sds.shape) - 1)))
        out[k] = NamedSharding(
            mesh, spec_for(ax[:len(sds.shape)], tuple(sds.shape), mesh, policy))
    return out


def policy_for(cfg: ArchConfig, mesh: Mesh, *, shape_kind: str = "train",
               batch: int = 0) -> ShardingPolicy:
    """Default policy per arch size and scenario (the baseline plan).

    * FSDP for ≥30B-param archs (params won't fit replicated per-DP-group).
    * Sequence sharding when the batch can't cover the DP axes (long ctx).
    """
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    big = cfg.param_count() > 30e9
    dp_size = 1
    for a in dp_axes:
        dp_size *= mesh.shape[a]
    seq_shard = batch > 0 and batch < dp_size
    return ShardingPolicy(dp_axes=dp_axes, fsdp=big, seq_shard=seq_shard)
