"""Logical-axis sharding rules (T5X/MaxText style) → GSPMD PartitionSpecs.

Model code annotates tensors with *logical* axis names; one rules table maps
them to mesh axes.  Changing the parallelism layout (the §Perf hillclimb
lever) means editing a rules dict, not the model.

Default layout on the (pod, data, model) mesh:
  batch      → (pod, data)   data parallel across pods and the data axis
  fsdp       → data          weight shards gathered per layer (ZeRO-3 style)
  heads/mlp/experts/vocab → model   tensor/expert parallel
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()

def shard_map(fn, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with the varying-axes check off by default.  The one
    entry point for every SPMD region in the repo (gradient compression,
    campaign case-sharding)."""
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check
    )


DEFAULT_RULES: dict[str, Any] = {
    "batch": ("pod", "data"),
    "seq": None,
    "embed": None,
    "kv": None,
    "heads": "model",
    "kv_heads": "model",
    "qk": None,
    "mlp": "model",
    "moe_mlp": None,          # expert FF dim; takes "model" when experts can't
    "experts": "model",
    "expert_cap": ("pod", "data"),  # MoE capacity dim follows tokens
    "vocab": "model",
    "fsdp": "data",
    "layers": None,
    "conv": None,
    "state": None,
    "kv_seq": None,           # decode caches: sequence-sharded (flash-decoding)
    "act_seq": None,          # sequence parallelism: residual stream between
    "ssm_heads": "model",     # blocks sharded over model (Megatron-SP)
    "enc_seq": None,
    "q_per_kv": None,         # GQA group dim: carries head parallelism when
    "attn_q": None,           # kv heads can't; attn_q = split-Q fallback
    "kv_batch": ("pod", "data"),  # decode-cache batch dim (≠ activation batch)
}


def rules_for(
    cfg, mesh, *, kind: str = "train", global_batch: int = 0, seq_len: int = 0
) -> dict[str, Any]:
    """Derive per-arch/per-shape rules from divisibility on this mesh.

    Every mesh axis used to shard a tensor dim must divide it; where the
    canonical choice doesn't divide (e.g. 8 kv heads on a 16-way model
    axis) the rule falls back: heads→replicated, expert FF→model,
    decode-cache sequence→model (flash-decoding style split-S).
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    model = sizes.get("model", 1)
    rules = dict(DEFAULT_RULES)

    # --- batch: largest (pod, data) prefix that divides the global batch
    dp = [a for a in ("pod", "data") if a in sizes]
    batch_axes: tuple = ()
    for k in range(len(dp), 0, -1):
        prod = 1
        for a in dp[:k]:
            prod *= sizes[a]
        if global_batch and global_batch % prod == 0:
            batch_axes = tuple(dp[:k])
            break
    rules["batch"] = batch_axes or None
    rules["expert_cap"] = batch_axes or None

    div = lambda n: n and n % model == 0
    rules["heads"] = "model" if div(cfg.n_heads) else None
    rules["kv_heads"] = "model" if div(cfg.n_kv_heads) else None
    rules["vocab"] = "model" if div(cfg.vocab_size) else None

    # all dims tagged "mlp" for this family must divide the model axis
    mlp_dims = [cfg.d_ff] if cfg.d_ff else []
    if cfg.ssm_state:
        d_inner = cfg.ssm_expand * cfg.d_model
        G, N, H = cfg.ssm_groups, cfg.ssm_state, d_inner // cfg.ssm_headdim
        conv_dim = d_inner + 2 * G * N
        mlp_dims += [d_inner, conv_dim, 2 * d_inner + 2 * G * N + H]
    if cfg.n_shared_experts:
        mlp_dims += [(cfg.moe_d_ff or cfg.d_ff) * cfg.n_shared_experts]
    rules["mlp"] = "model" if mlp_dims and all(d % model == 0 for d in mlp_dims) else None

    if cfg.n_experts:
        if cfg.n_experts % model == 0:
            rules["experts"], rules["moe_mlp"] = "model", None
        else:
            F = cfg.moe_d_ff or cfg.d_ff
            rules["experts"] = None
            rules["moe_mlp"] = "model" if F % model == 0 else None
    if cfg.ssm_state:
        d_inner = cfg.ssm_expand * cfg.d_model
        rules["ssm_heads"] = "model" if (d_inner // cfg.ssm_headdim) % model == 0 else None

    # attention-internal parallelism when kv heads can't cover the model
    # axis: prefer sharding the q-per-kv (GQA group) dim; else split-Q
    # (query-block dim) — both keep the blocked flash fully model-parallel
    if cfg.n_kv_heads:
        G = cfg.n_heads // max(1, cfg.n_kv_heads)
        if rules["kv_heads"] is None and G % model == 0 and G > 0:
            rules["q_per_kv"] = "model"
        elif rules["kv_heads"] is None and kind != "decode":
            rules["attn_q"] = "model"
    rules["kv_batch"] = batch_axes or None
    if kind == "decode":
        # split-S decode attention: shard caches along sequence when kv
        # heads can't cover the model axis (keeps per-chip KV ≤ HBM)
        rules["kv_seq"] = None if rules["kv_heads"] else "model"
        # activations replicate over the data axes: decode matmuls then
        # contract the data-sharded weight dim with tiny activation psums
        # instead of all-gathering the weights every token (§Perf cell 3:
        # 94 GiB → activation-sized collectives per step on llama3-405b)
        rules["batch"] = None
        rules["expert_cap"] = None
    if kind in ("train", "prefill") and seq_len and seq_len % model == 0:
        # sequence parallelism: the per-layer saved residuals (the dominant
        # training-memory term) shard over the model axis between blocks
        rules["act_seq"] = "model"
    return rules


def current_mesh() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


def current_rules() -> dict[str, Any]:
    return getattr(_state, "rules", DEFAULT_RULES)


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh], rules: Optional[dict] = None):
    """Activate a mesh + rules for sharding constraints inside model code."""
    prev = (current_mesh(), current_rules())
    _state.mesh = mesh
    _state.rules = dict(DEFAULT_RULES, **(rules or {}))
    try:
        yield
    finally:
        _state.mesh, _state.rules = prev


def spec_for(*logical: Optional[str], rules: Optional[dict] = None) -> P:
    """PartitionSpec from logical axis names, dropping mesh axes not present."""
    rules = rules or current_rules()
    mesh = current_mesh()
    names = set(mesh.axis_names) if mesh is not None else None
    out = []
    for ax in logical:
        m = rules.get(ax) if ax else None
        if m is None:
            out.append(None)
            continue
        axes = (m,) if isinstance(m, str) else tuple(m)
        if names is not None:
            axes = tuple(a for a in axes if a in names)
        out.append(axes[0] if len(axes) == 1 else (axes if axes else None))
    return P(*out)


def constrain(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """with_sharding_constraint by logical axes; no-op without a mesh."""
    mesh = current_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec_for(*logical)))


def named_sharding(*logical: Optional[str], mesh: Optional[Mesh] = None) -> NamedSharding:
    mesh = mesh or current_mesh()
    assert mesh is not None, "no active mesh"
    return NamedSharding(mesh, spec_for(*logical))


def tree_shardings(spec_tree: Any, mesh: Mesh, rules: Optional[dict] = None) -> Any:
    """Pytree of logical-axis tuples → pytree of NamedShardings."""
    rules = dict(DEFAULT_RULES, **(rules or {}))
    names = set(mesh.axis_names)

    def to_sharding(logical):
        out = []
        for ax in logical:
            m = rules.get(ax) if ax else None
            if m is None:
                out.append(None)
                continue
            axes = (m,) if isinstance(m, str) else tuple(m)
            axes = tuple(a for a in axes if a in names)
            out.append(axes[0] if len(axes) == 1 else (axes if axes else None))
        return NamedSharding(mesh, P(*out))

    return jax.tree_util.tree_map(
        to_sharding, spec_tree, is_leaf=lambda x: isinstance(x, tuple)
    )
