"""Logical-axis sharding rules -> PartitionSpec / NamedSharding.

Model code annotates activations with *logical* axis names; the rules map them
to physical mesh axes.  This keeps the model mesh-agnostic: the same code runs
on (data, model), (pod, data, model), or a single device (rules=None).

Physical layout (DESIGN.md §4):
  batch          -> (pod?, data)              data parallel
  heads/ff/vocab/experts -> model             tensor / expert parallel
  fsdp (weight dim 0)    -> (pod?, data)      ZeRO-style param+opt sharding
Any axis that does not divide the dimension is dropped (guarded specs), so
e.g. batch=1 decode falls back gracefully.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.utils import path_str


def fit_spec(shape: Tuple[int, ...], spec: P, mesh: Mesh) -> P:
    """Drop mesh axes that do not evenly divide their dim; pad/trim rank."""
    entries = tuple(spec) + (None,) * (len(shape) - len(spec))
    entries = entries[:len(shape)]
    fixed = []
    for dim, ax in zip(shape, entries):
        if ax is None:
            fixed.append(None)
            continue
        axs = (ax,) if isinstance(ax, str) else tuple(ax)
        keep, size = [], 1
        for a in axs:
            if a not in mesh.axis_names:
                continue
            n = mesh.shape[a]
            if dim % (size * n) == 0:
                keep.append(a)
                size *= n
        fixed.append(tuple(keep) if len(keep) > 1 else (keep[0] if keep else None))
    return P(*fixed)


@dataclass
class Rules:
    mesh: Optional[Mesh]
    table: Dict[str, Tuple[str, ...]] = field(default_factory=dict)

    def spec(self, *logical) -> P:
        if self.mesh is None:
            return P()
        axes, used = [], set()
        for name in logical:
            phys = self.table.get(name) if name else None
            if not phys:
                axes.append(None)
                continue
            avail = tuple(a for a in phys
                          if a not in used and a in self.mesh.axis_names)
            used.update(avail)
            if not avail:
                axes.append(None)
            else:
                axes.append(avail if len(avail) != 1 else avail[0])
        return P(*axes)

    def sharding(self, shape, *logical) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, fit_spec(shape, self.spec(*logical),
                                                 self.mesh))

    def constrain(self, x, *logical):
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, self.sharding(x.shape, *logical))


def make_rules(mesh: Optional[Mesh]) -> Rules:
    if mesh is None:
        return Rules(mesh=None)
    has_pod = "pod" in mesh.axis_names
    dp = ("pod", "data") if has_pod else ("data",)
    table = {
        "batch": dp,
        "fsdp": dp,
        "embed_fsdp": dp,
        "heads": ("model",),
        "kv_heads": ("model",),
        "ff": ("model",),
        "vocab": ("model",),
        "experts": ("model",),
        "act_seq": ("model",),   # sequence-parallel residual stream
    }
    return Rules(mesh=mesh, table=table)


# ------------------------------------------------------------ param specs
def param_spec(path, shape: Tuple[int, ...], rules: Rules) -> P:
    """Sharding spec for one parameter, keyed by its pytree path."""
    if rules.mesh is None:
        return P()
    name = path if isinstance(path, str) else path_str(path)
    leaf = name.rsplit("/", 1)[-1]
    stacked = "layers" in name  # leading repeat dim from the layer scan
    base_rank = len(shape) - (1 if stacked else 0)

    if base_rank <= 1 or "bias" in leaf or "scale" in leaf or leaf in (
            "A_log", "D", "dt_bias", "b_if", "b_gates"):
        logical: Tuple[Optional[str], ...] = (None,) * base_rank
    elif leaf == "embedding":
        logical = ("vocab", "embed_fsdp")
    elif leaf == "unembed":
        logical = ("embed_fsdp", "vocab")
    elif leaf in ("wq", "wk", "wv"):
        logical = ("embed_fsdp", "heads")
    elif leaf == "wo_attn":
        logical = ("heads", "embed_fsdp")
    elif leaf.startswith("experts_"):
        logical = ("experts", "fsdp", None)
    elif leaf == "router":
        logical = ("embed_fsdp", None)
    elif leaf in ("wi", "wg", "ff_wi", "in_proj", "w_gates", "w_if"):
        logical = ("embed_fsdp", "ff")
    elif leaf in ("wo", "ff_wo", "out_proj"):
        logical = ("ff", "embed_fsdp")
    elif leaf == "conv_w":
        logical = (None, "ff")
    elif leaf == "r_gates":
        logical = ("heads", None, None)
    else:
        logical = ("embed_fsdp",) + (None,) * (base_rank - 1)

    spec = rules.spec(*logical)
    if stacked:
        spec = P(None, *spec)
    return fit_spec(shape, spec, rules.mesh)


def tree_specs(tree, spec_fn, rules: Rules):
    """Map a spec function over a pytree of ShapeDtypeStructs/arrays."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = [NamedSharding(rules.mesh, spec_fn(path_str(p), l.shape, rules))
           for p, l in flat]
    return jax.tree_util.tree_unflatten(treedef, out)
