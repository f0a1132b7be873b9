"""Logical-axis -> mesh-axis sharding rules (GSPMD front-end).

The mesh is ("data","model") for one pod and ("pod","data","model") for the
multi-pod run (DESIGN.md §6). Logical parameter axes map to mesh axes through
``Rules``; a mapping is silently dropped (replicated) when the dimension is
not divisible by the mesh axis — this is how GQA KV heads (2/8/16) degrade
gracefully on a 16-wide model axis.

Beyond-paper knobs that §Perf iterates on live here: which logical axes get
FSDP ("data") sharding, whether experts are expert-parallel, etc.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.params import Decl, is_decl

# Default logical->mesh rules. Order inside the tuple = priority; all axes
# that divide the dim evenly are used together (e.g. ("data","model")).
DEFAULT_RULES: Dict[str, Tuple[str, ...]] = {
    "batch": ("pod", "data"),  # activations / caches: data parallel
    "seq": (),
    "embed": ("fsdp",),        # FSDP: shard d_model of weights over data axis
    "qheads": ("model",),      # tensor parallel over attention heads
    "kvheads": ("model",),     # sharded only when kv_heads % model == 0
    "headdim": (),
    "ffn": ("model",),         # Megatron-style FFN split
    "vocab": ("model",),       # embedding/logits vocab split
    "experts": ("model",),     # expert parallelism
    "ssm_inner": ("model",),   # mamba2 d_inner / heads split
    "ssm_heads": ("model",),
    "state": (),
    "lru": ("model",),         # RG-LRU width split
    "layers": (),              # scan axis, never sharded
    "window": (),
}


@dataclass(frozen=True)
class Rules:
    table: Dict[str, Tuple[str, ...]] = field(default_factory=lambda: dict(DEFAULT_RULES))
    fsdp: bool = True                   # resolve "fsdp" pseudo-axis -> data axis
    fsdp_axes: Tuple[str, ...] = ("data",)
    dp_axes: Tuple[str, ...] = ("pod", "data")   # batch axes (filtered by mesh)

    def updated(self, **table_updates) -> "Rules":
        t = dict(self.table)
        t.update(table_updates)
        return replace(self, table=t)

    def resolve(self, logical: Optional[str], mesh: Mesh, dim: int):
        """Mesh axes for one logical dim, dropping non-dividing axes."""
        if logical is None:
            return None
        axes = []
        for a in self.table.get(logical, ()):  # unknown logical -> replicated
            if a == "fsdp":
                if not self.fsdp:
                    continue
                cand = [x for x in self.fsdp_axes if x in mesh.shape]
            else:
                cand = [a] if a in mesh.shape else []
            for c in cand:
                if c not in axes and dim % (np.prod([mesh.shape[x] for x in axes + [c]])) == 0:
                    axes.append(c)
        if not axes:
            return None
        return tuple(axes) if len(axes) > 1 else axes[0]

    def spec_for(self, decl: Decl, mesh: Mesh) -> P:
        used = set()
        parts = []
        for dim, logical in zip(decl.shape, decl.logical):
            r = self.resolve(logical, mesh, dim)
            # a mesh axis may appear at most once per spec
            if r is not None:
                rr = r if isinstance(r, tuple) else (r,)
                rr = tuple(a for a in rr if a not in used)
                used.update(rr)
                r = rr if len(rr) > 1 else (rr[0] if rr else None)
                if r == ():
                    r = None
            parts.append(r)
        return P(*parts)

    def batch_axes(self, mesh: Mesh):
        axes = tuple(a for a in self.dp_axes if a in mesh.shape)
        return axes if axes else None

    def batch_spec(self, mesh: Mesh, ndim: int, batch_dim: int = 0) -> P:
        parts = [None] * ndim
        parts[batch_dim] = self.batch_axes(mesh)
        return P(*parts)


# ------------------------------------------------- activation constraints --
# Launch-time context: when set, model code can pin activation shardings by
# logical axis name (the beyond-paper §Perf levers — vocab-sharded logits,
# joint-mesh attention resharding). Model code never imports mesh objects;
# it calls ``constrain_logical`` which is a no-op unless the launcher
# installed a context.
_ACT_CTX: dict = {"mesh": None, "rules": None}

ACT_RULES: Dict[str, Tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "batch_joint": ("pod", "data", "model"),  # attention batch resharding
    "vocab": ("model",),
    "seq": (),
}


def set_activation_context(mesh: Optional[Mesh], rules: Optional[Rules] = None):
    """Install (or clear, with None) the activation-sharding context."""
    _ACT_CTX["mesh"] = mesh
    _ACT_CTX["rules"] = rules or (Rules() if mesh is not None else None)


def activation_context_mesh() -> Optional[Mesh]:
    return _ACT_CTX["mesh"]


def constrain_logical(x, logical: Tuple[Optional[str], ...]):
    """with_sharding_constraint by logical axis names; no-op without an
    installed context. Non-dividing axes degrade to replicated."""
    mesh = _ACT_CTX["mesh"]
    if mesh is None:
        return x
    rules = _ACT_CTX["rules"]
    used = set()
    parts = []
    for dim, l in zip(x.shape, logical):
        if l is None:
            parts.append(None)
            continue
        axes = []
        for a in ACT_RULES.get(l, rules.table.get(l, ())):
            if a in mesh.shape and a not in used and \
                    dim % int(np.prod([mesh.shape[b] for b in axes + [a]])) == 0:
                axes.append(a)
        used.update(axes)
        parts.append(tuple(axes) if len(axes) > 1 else (axes[0] if axes else None))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*parts)))


def attn_batch_split_ok(global_batch: int) -> bool:
    """The explicit batch-split attention needs the per-data-shard batch
    to divide the model axis."""
    mesh = _ACT_CTX["mesh"]
    if mesh is None or "model" not in mesh.shape:
        return False
    rules = _ACT_CTX["rules"]
    dp = int(np.prod([mesh.shape[a] for a in rules.dp_axes
                      if a in mesh.shape]))
    local = global_batch // dp
    return local % mesh.shape["model"] == 0


def attn_needs_batch_reshard(n_heads: int) -> bool:
    """True when TP cannot split the heads on the installed mesh (the
    qwen2-1.5b 12-head / whisper 20-head / paligemma 8-head cases) — then
    resharding the batch over the joint mesh recovers the lost parallelism."""
    mesh = _ACT_CTX["mesh"]
    if mesh is None or mesh.shape.get("model", 1) <= 1:
        return False
    return n_heads % mesh.shape["model"] != 0


def pspecs(decls, mesh: Mesh, rules: Rules):
    """PartitionSpec tree matching a Decl tree."""
    return jax.tree.map(lambda d: rules.spec_for(d, mesh), decls, is_leaf=is_decl)


def shardings(decls, mesh: Mesh, rules: Rules):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        pspecs(decls, mesh, rules))


def constrain(x, mesh: Mesh, spec: P):
    """with_sharding_constraint that is a no-op off-mesh (CPU tests)."""
    try:
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    except Exception:
        return x
