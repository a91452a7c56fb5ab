"""Logical-axis → mesh-axis sharding rules.

Model code annotates every param/state leaf with logical axis names
(tuples like ("embed", "q_heads", "head_dim")); this module maps them to
``PartitionSpec``s for a given mesh. Strategy (MaxText-style):

  * tensor-parallel axes (heads/mlp/vocab/experts) → "model"
  * FSDP: the d_model ("embed") weight axis → "data" (weights gathered
    per-layer inside the scan; optimizer state inherits → ZeRO-3)
  * batch → all data-parallel axes ("pod","data")
  * long-context decode (batch=1): kv_seq → "data" (flash-decoding-style
    partial-softmax combine emerges from SPMD reductions)

A mesh axis may appear at most once in a PartitionSpec; when two logical
axes map to the same mesh axis, the later one is dropped (replicated) —
e.g. zamba's (embed, embed) projections.
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> mesh axis (None = replicate)
BASE_RULES: dict[str, str | tuple[str, ...] | None] = {
    "layers": None,
    "embed": ("pod", "data"),  # FSDP; extends across pods when present
                               # (132B-class state only fits multi-pod)
    "heads_embed": "model",   # square d×d projections' output side (rwkv)
    "q_heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    # projection input dims that must stay replicated (small models where
    # FSDP-sharding the contraction dim makes XLA psum full activations over
    # the data axis instead of gathering the far smaller weight — measured
    # 12 GB/step of f32 activation all-reduces on rwkv6 before this)
    "act_in": None,
    # the embedding table's d_model axis stays replicated: FSDP-sharding it
    # puts the contraction dim of the LM head on the data axis and XLA emits
    # full-logits all-gathers/all-reduces (measured: 24 GB/op on internlm2)
    "table_embed": None,
    "mlp": "model",
    "vocab": "model",
    "experts": "model",
    "conv": None,
    "state": None,
    "lora": None,
    "heads": "model",
    # activations / cache
    "batch": ("pod", "data"),
    "seq": None,
    "kv_seq": None,
}

# decode: the cache updates in place each step (donated buffers), so its
# kv_seq dim must stay UNSHARDED — a dynamic-update-slice on a sharded dim
# makes SPMD copy the whole cache through temps (measured +13.4 GB/step on
# qwen decode_32k). Shard kv_heads over "model" instead, with head_dim as
# the dedupe fallback when heads don't divide (qwen's kv=20 ∤ 16 shards
# head_dim=128); attention then contracts hd with a small psum.
DECODE_OVERRIDES = {
    "kv_seq": None,
    "head_dim": "model",
}

LONG_CONTEXT_OVERRIDES = {
    "batch": None,                    # batch=1: cannot shard
    "kv_seq": ("data", "model"),      # shard the long KV/sequence instead
}


def _mesh_axes(mesh: Mesh, name) -> tuple[str, ...]:
    if name is None:
        return ()
    names = name if isinstance(name, tuple) else (name,)
    return tuple(n for n in names if n in mesh.axis_names)


def spec_for(mesh: Mesh, logical: tuple, rules: dict | None = None,
             dims: tuple[int, ...] | None = None) -> P:
    """Build a PartitionSpec from logical axes; dedupe repeated mesh axes.

    If ``dims`` is given, any mapping where the dim is not divisible by the
    mesh-axis size is dropped (replicated) — keeps odd dims lowerable.
    """
    rules = rules or BASE_RULES
    used: set[str] = set()
    out = []
    for i, ax in enumerate(logical):
        mapped = _mesh_axes(mesh, rules.get(ax) if ax is not None else None)
        mapped = tuple(m for m in mapped if m not in used)
        if mapped and dims is not None:
            total = 1
            for m in mapped:
                total *= mesh.shape[m]
            if dims[i] % total:
                mapped = ()
        if mapped:
            used.update(mapped)
            out.append(mapped if len(mapped) > 1 else mapped[0])
        else:
            out.append(None)
    return P(*out)


def tree_shardings(mesh: Mesh, spec_tree, shape_tree=None, *,
                   overrides: dict | None = None):
    """Map a logical-spec tree (+ optional matching ShapeDtypeStruct tree for
    divisibility checks) to a NamedSharding tree."""
    rules = dict(BASE_RULES)
    if overrides:
        rules.update(overrides)

    is_leaf = lambda x: isinstance(x, tuple) and all(
        isinstance(e, (str, type(None))) for e in x)

    if shape_tree is None:
        return jax.tree.map(
            lambda spec: NamedSharding(mesh, spec_for(mesh, spec, rules)),
            spec_tree, is_leaf=is_leaf)
    return jax.tree.map(
        lambda spec, sds: NamedSharding(
            mesh, spec_for(mesh, spec, rules, dims=sds.shape)),
        spec_tree, shape_tree, is_leaf=is_leaf)


def constrain(x, *logical, overrides: dict | None = None):
    """Activation sharding constraint by logical axis names; no-op outside a
    mesh context (host tests) or when a dim doesn't divide its mesh axes.

    XLA's sharding propagation can silently replicate activations when an
    adjacent weight axis fails to shard (measured: qwen1.5's 20 heads on the
    16-way model axis replicated whole-batch attention activations — 832 GB
    buffers). Pinning the batch/head layout at block boundaries prevents it.
    """
    import jax.numpy as jnp  # local: avoid cycle at module import

    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or not mesh.axis_names:
        return x
    rules = dict(BASE_RULES)
    if overrides:
        rules.update(overrides)
    spec = spec_for(mesh, logical, rules, dims=x.shape)
    return jax.lax.with_sharding_constraint(x, spec)


def data_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def batch_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P(data_axes(mesh)))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
