"""Logical-axis sharding: model code declares WHAT each dim is, the mesh
layer decides WHERE it goes (MaxText-style logical axis rules).

Every parameter initializer returns (array, logical_axes) where
logical_axes is a tuple of strings, one per dim.  `resolve_spec` maps
logical names -> physical mesh axes with divisibility checking, so the
same model code runs on the 1-device CPU smoke mesh, the 16x16 pod and
the 2x16x16 multi-pod mesh without edits.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

# logical axis -> preferred physical axes, in priority order.
# "fsdp" rules shard parameters over the data axis (ZeRO-3 style); XLA
# all-gathers them per scan step, which is what keeps grok-1-314b's fp32
# master + Adam state inside the 16 GB/chip HBM budget (DESIGN.md §10).
DEFAULT_RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "seq": (),                    # activations: unsharded by default
    "seq_shard": ("data",),       # long-context KV/state sharding (SP)
    "embed": ("data",),           # fsdp dim of params
    "embed_no_fsdp": (),
    "vocab": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "head_dim": (),
    "ffn": ("model",),
    "experts": ("model",),        # EP
    "expert_ffn": ("model",),     # fallback TP when n_experts < model axis
                                  # (grok-1: 8 experts on a 16-way axis)
    "ssm_inner": ("model",),
    "ssm_state": (),
    "conv": (),
    "cycles": (),                 # stacked scan layers: never sharded
    "frames": (),
    # activation constraints (see constrain() below)
    "act_batch": ("pod", "data"),
    "act_vocab": ("model",),
    "act_ffn": ("model",),
    "act_heads": ("model",),
    "act_experts": ("model",),
    "act_expert_cap": ("data",),  # MoE dispatch-capacity dim
    "act_expert_flat": ("model", "data"),  # flattened (E*C) dispatch dim
    "act_tokens": ("pod", "data"),         # flattened (B*S) token dim
    "act_moe_groups": ("pod", "data"),     # GShard routing-group dim
    # geostat distributed Cholesky (core/distributed.py)
    "geo_rows": ("data",),
    "geo_cols": ("model",),
    # fori variant: traced-offset column slices forbid column sharding
    # inside the loop carry, so rows take BOTH axes (1-D x 256-way)
    "geo_rows2d": ("data", "model"),
    None: (),
}

# ---------------------------------------------------------------------
# Activation sharding constraints.
#
# GSPMD propagates parameter shardings into activations, but with FSDP
# ("embed" over data) the propagation pass can resolve the conflict the
# wrong way: replicate the *batch* over data and keep weights sharded --
# observed as 141 GiB/chip activation buffers on llama3.2-1b:train_4k
# (EXPERIMENTS.md §Perf iteration 1).  constrain() pins the batch/ffn/
# vocab dims of key activations.  It is a no-op unless the launcher has
# installed a mesh (set_activation_mesh), so model code stays mesh-free
# and smoke tests on 1 device are unaffected.
# ---------------------------------------------------------------------

_ACTIVATION_MESH: list = [None]


def set_activation_mesh(mesh):
    """Install (or clear, with None) the mesh used by constrain().

    with_sharding_constraint only accepts Auto mesh axes; the constructors
    in launch/mesh.py build them (`jax.make_mesh` defaults to Explicit).
    """
    if mesh is not None and any(t != AxisType.Auto for t in mesh.axis_types):
        raise ValueError(f"constrain() needs Auto mesh axes, got "
                         f"{mesh.axis_types}; build the mesh with "
                         f"repro.launch.mesh")
    _ACTIVATION_MESH[0] = mesh


def constrain(x, logical_axes: str, *, allow_uneven: bool = False):
    mesh = _ACTIVATION_MESH[0]
    if mesh is None:
        return x
    spec = resolve_spec(logical_axes, mesh, shape=x.shape,
                        allow_uneven=allow_uneven)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def ax(*names: str) -> str:
    """Pack logical dim names into a single pytree-leaf string.

    A tuple would itself be a pytree (breaking tree.map against the params
    tree), so logical axes travel as space-joined strings: ax("embed",
    "heads", "head_dim") -> "embed heads head_dim".  "." means unsharded.
    """
    return " ".join(n if n is not None else "." for n in names)


def resolve_spec(logical_axes: str, mesh: Mesh, rules=None,
                 shape=None, allow_uneven: bool = False) -> P:
    """Map packed logical axis names to a PartitionSpec on `mesh`.

    Divisibility fallback: a physical axis is only used if the dim size is
    divisible by the axis size (checked when `shape` is provided).
    allow_uneven (activation constraints only): accept non-divisible dims
    when dim >= axis size -- GSPMD pads (llava's 56 heads on a 16-way
    axis cost <13% padding vs 16x replication).
    """
    rules = rules or DEFAULT_RULES
    names = logical_axes.split(" ") if logical_axes else []
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    used = set()
    spec = []
    for i, name in enumerate(names):
        cands = rules.get(name, ()) if name != "." else ()
        placed = ()
        for axname in cands:
            if axname not in axis_sizes or axname in used:
                continue
            if shape is not None and shape[i] % axis_sizes[axname] != 0:
                if not (allow_uneven and shape[i] >= axis_sizes[axname]):
                    continue
            placed = placed + (axname,)
            used.add(axname)
        if len(placed) == 0:
            spec.append(None)
        elif len(placed) == 1:
            spec.append(placed[0])
        else:
            spec.append(placed)
    return P(*spec)


def tree_resolve_shardings(params, logical_tree, mesh: Mesh, rules=None):
    """params pytree + parallel logical-axes pytree -> NamedSharding tree."""
    def one(arr, axes):
        spec = resolve_spec(axes, mesh, rules, shape=arr.shape)
        return NamedSharding(mesh, spec)
    return jax.tree.map(one, params, logical_tree)


def batch_spec(mesh: Mesh, *, seq_sharded: bool = False) -> P:
    """Input batch sharding: batch over (pod, data); optionally the seq dim
    over data (long-context cells where batch < n_data)."""
    axes = [a for a in ("pod", "data") if a in mesh.axis_names]
    if seq_sharded:
        return P(None, tuple(a for a in ("data",) if a in mesh.axis_names))
    return P(tuple(axes))
