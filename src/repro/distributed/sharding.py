"""Logical-axis -> mesh-axis rules, per workload kind.

One table drives everything: parameter shardings (pjit in_shardings),
optimizer-state shardings (mirrors params), activation constraints
(models/layers.shard), and batch shardings.

Production layout (DESIGN.md §7):
  * params: 2-D sharded — "embed" over the FSDP axes (data [+pod]),
    "heads_flat"/"ffn"/"vocab"/"experts" over "model" (TP/EP);
  * activations: "batch" over FSDP axes; TP internals over "model";
  * decode KV caches: "kv_seq" over "model" (sequence-parallel decode —
    GQA kv-head counts don't divide a 16-way model axis);
  * long_500k (global_batch=1): batch unshardable, so "kv_seq" spreads
    over ("data","model") = the whole pod.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec


def make_rules(mesh: Mesh, kind: str = "train",
               long_context: bool = False) -> Dict[str, Any]:
    axes = mesh.axis_names
    fsdp: Any = ("pod", "data") if "pod" in axes else "data"
    rules: Dict[str, Any] = {
        "batch": fsdp,
        "embed": fsdp,          # FSDP parameter dim
        "embed_out": None,
        "vocab": "model",
        "heads_flat": "model",
        "heads": "model",
        "ffn": "model",
        "experts": "model",
        "expert_ffn": None,
        "expert_cap": fsdp,
        "kv_seq": "model" if kind == "decode" else None,
        "layers": None,
    }
    if kind == "decode" and long_context:
        rules["batch"] = None
        rules["expert_cap"] = None
        rules["kv_seq"] = ("data", "model")
    return rules


def logical_to_spec(logical: PartitionSpec,
                    rules: Dict[str, Any]) -> PartitionSpec:
    """Map a PartitionSpec of *logical* names to mesh axes."""
    out = []
    for entry in logical:
        if entry is None:
            out.append(None)
        else:
            out.append(rules.get(entry))
    return PartitionSpec(*out)


def to_named_sharding(mesh: Mesh, logical_tree,
                      rules: Dict[str, Any]):
    """Tree of logical PartitionSpecs -> tree of NamedShardings."""
    return jax.tree_util.tree_map(
        lambda sp: NamedSharding(mesh, logical_to_spec(sp, rules)),
        logical_tree,
        is_leaf=lambda x: isinstance(x, PartitionSpec))


def batch_sharding(mesh: Mesh, ax_tree, rules: Dict[str, Any]):
    """Tree of logical-axes tuples (or PartitionSpecs) -> NamedShardings."""

    def conv(ax):
        if isinstance(ax, PartitionSpec):
            return NamedSharding(mesh, logical_to_spec(ax, rules))
        spec = PartitionSpec(
            *[rules.get(a) if a is not None else None for a in ax])
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map(
        conv, ax_tree,
        is_leaf=lambda x: isinstance(x, (tuple, PartitionSpec)))
