"""Shared transformer building blocks.

Pure functions over parameter pytrees (see models/common.py for the schema
system). Everything is written to live inside a ``lax.scan`` over stacked
layer parameters, so no Python-level per-layer state is allowed.

Attention memory policy: full (S, S) score materialization is never allowed
for long sequences — ``chunked_attention`` scans over query chunks and is
exact (full key rows per chunk), keeping activation footprint
O(chunk * S) instead of O(S^2). The Pallas flash-attention kernel
(repro.kernels.flash_attention) is the TPU-optimized path; this file is the
portable/jnp path used for CPU smoke tests and as the lowering default
(see DESIGN.md §Kernels).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MoEConfig

PS = jax.sharding.PartitionSpec


def _current_mesh_axes() -> Tuple[str, ...]:
    """Axis names of the mesh entered with ``jax.set_mesh``, or ()."""
    return tuple(n for n, _ in jax.sharding.get_abstract_mesh().shape_tuple)


import os as _os

# layout profile (see launch/dryrun.py REPRO_LAYOUT): model code marks the
# batch dim with the literal ("pod", "data") tuple; under the pure-DP
# profile that resolves to ("data", "model") and model-axis activation
# hints are dropped (a replicated-parameter layout must not reshard
# activations onto the model axis).
_BATCH_AXES = tuple(
    _os.environ.get("REPRO_BATCH_AXES", "pod,data").split(",")
)
_MODEL_HINTS = _os.environ.get("REPRO_MODEL_HINTS", "1") != "0"


def shard_hint(x: jax.Array, *axes) -> jax.Array:
    """Best-effort sharding constraint.

    Filters requested logical axes against the active mesh's axis names and
    becomes a no-op when no mesh is active (CPU smoke tests) — so model code
    can state its preferred layout unconditionally.
    """
    names = _current_mesh_axes()
    if not names:
        return x
    clean = []
    for a in axes:
        if isinstance(a, (tuple, list)) and tuple(a) == ("pod", "data"):
            a = _BATCH_AXES  # batch-dim marker: resolve per layout profile
        elif a == "model" and not _MODEL_HINTS:
            a = None
        if a is None:
            clean.append(None)
        elif isinstance(a, (tuple, list)):
            kept = tuple(n for n in a if n in names)
            clean.append(kept if kept else None)
        else:
            clean.append(a if a in names else None)
    try:
        return jax.lax.with_sharding_constraint(x, PS(*clean))
    except Exception:
        return x


# ---------------------------------------------------------------------------
# norms / rope / mlp
# ---------------------------------------------------------------------------


# --- beyond-paper optimization (§Perf hillclimb 1): gather FSDP-sharded
# weights at their use site instead of letting the partitioner reduce
# activations. With 2D (data x model) parameter sharding, a contraction
# over the data-sharded dim otherwise lowers to a full-activation psum per
# projection (~200MB each on the 123B arch); re-sharding the weight to
# model-only costs one small all-gather of the layer's weight shards
# (~88MB total) and leaves exactly the two Megatron-mandatory psums per
# block. Toggle via env REPRO_GATHER_WEIGHTS=0 for the baseline lowering.
GATHER_WEIGHTS = _os.environ.get("REPRO_GATHER_WEIGHTS", "1") != "0"


def use_weight(w: jax.Array, *model_axes) -> jax.Array:
    """Constrain a parameter to model-axis-only sharding for compute.

    ``model_axes``: one entry per dim — "model" to keep TP sharding, None
    to gather. No-op when GATHER_WEIGHTS is disabled or no mesh is active.
    """
    if not GATHER_WEIGHTS:
        return w
    return shard_hint(w, *model_axes)


def rmsnorm(x: jax.Array, gamma: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * gamma.astype(jnp.float32)).astype(x.dtype)


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., S, hd/2)
    ang = ang[..., None, :]  # (..., S, 1, hd/2) — broadcast over heads
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array) -> jax.Array:
    g = jnp.einsum("...d,df->...f", x, use_weight(w_gate, None, "model"))
    u = jnp.einsum("...d,df->...f", x, use_weight(w_up, None, "model"))
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    h = shard_hint(h, None, None, "model")
    return jnp.einsum("...f,fd->...d", h, use_weight(w_down, "model", None))


def gelu_mlp(x: jax.Array, w_in: jax.Array, b_in, w_out: jax.Array, b_out) -> jax.Array:
    w_in = use_weight(w_in, None, "model")
    w_out = use_weight(w_out, "model", None)
    h = jnp.einsum("...d,df->...f", x, w_in)
    if b_in is not None:
        h = h + b_in
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    h = shard_hint(h, None, None, "model")
    o = jnp.einsum("...f,fd->...d", h, w_out)
    if b_out is not None:
        o = o + b_out
    return o


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AttnParams:
    """View over one layer's attention weights (already layer-sliced)."""

    wq: jax.Array  # (d, H*hd)
    wk: jax.Array  # (d, KV*hd)
    wv: jax.Array  # (d, KV*hd)
    wo: jax.Array  # (H*hd, d)
    bq: Optional[jax.Array] = None
    bk: Optional[jax.Array] = None
    bv: Optional[jax.Array] = None
    q_norm: Optional[jax.Array] = None  # (hd,) qk-norm gains
    k_norm: Optional[jax.Array] = None


def project_qkv(
    cfg: ModelConfig, p: AttnParams, x: jax.Array, positions: Optional[jax.Array],
    *, rope: bool = True,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B, S, d) -> q: (B, S, H, hd), k/v: (B, S, KV, hd)."""
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,dh->bsh", x, use_weight(p.wq, None, "model"))
    k = jnp.einsum("bsd,dh->bsh", x, use_weight(p.wk, None, "model"))
    v = jnp.einsum("bsd,dh->bsh", x, use_weight(p.wv, None, "model"))
    if p.bq is not None:
        q, k, v = q + p.bq, k + p.bk, v + p.bv
    q = q.reshape(B, S, cfg.n_heads, hd)
    k = k.reshape(B, S, cfg.n_kv_heads, hd)
    v = v.reshape(B, S, cfg.n_kv_heads, hd)
    if p.q_norm is not None:
        q = rmsnorm(q, p.q_norm, cfg.norm_eps)
        k = rmsnorm(k, p.k_norm, cfg.norm_eps)
    if rope and positions is not None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _sdpa_chunk(
    q: jax.Array,  # (B, C, H, hd) one query chunk
    k: jax.Array,  # (B, S, KV, hd)
    v: jax.Array,  # (B, S, KV, hd)
    mask: Optional[jax.Array],  # (C, S) True = attend, or None
) -> jax.Array:
    B, C, H, hd = q.shape
    KV = k.shape[2]
    g = H // KV  # GQA group size
    qg = q.reshape(B, C, KV, g, hd)
    scores = jnp.einsum("bckgh,bskh->bkgcs", qg, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(hd).astype(jnp.float32)
    if mask is not None:
        scores = jnp.where(mask[None, None, None], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgcs,bskh->bckgh", w.astype(v.dtype), v)
    return out.reshape(B, C, H, hd)


def chunked_attention(
    q: jax.Array,  # (B, S, H, hd)
    k: jax.Array,  # (B, S_kv, KV, hd)
    v: jax.Array,
    *,
    causal: bool,
    q_offset: int = 0,
    chunk: int = 1024,
) -> jax.Array:
    """Exact attention, scanning over query chunks (memory O(chunk * S_kv)).

    ``q_offset``: position of q[0] relative to k[0] (for decode/cross cases).
    """
    B, S, H, hd = q.shape
    S_kv = k.shape[1]
    chunk = min(chunk, S)
    if S % chunk != 0:  # pad to a multiple (masked out)
        pad = chunk - S % chunk
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_chunks = q.shape[1] // chunk
    qs = q.reshape(B, n_chunks, chunk, H, hd).transpose(1, 0, 2, 3, 4)

    kv_pos = jnp.arange(S_kv)

    def body(carry, args):
        qc, idx = args
        if causal:
            q_pos = q_offset + idx * chunk + jnp.arange(chunk)
            mask = kv_pos[None, :] <= q_pos[:, None]
        else:
            mask = None
        return carry, _sdpa_chunk(qc, k, v, mask)

    _, outs = jax.lax.scan(body, 0, (qs, jnp.arange(n_chunks)))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, n_chunks * chunk, H, hd)
    return out[:, :S]


def decode_attention(
    q: jax.Array,  # (B, 1, H, hd)
    k_cache: jax.Array,  # (B, S_max, KV, hd) — S_max sharded over "model"
    v_cache: jax.Array,
    length: jax.Array,  # () or (B,) valid prefix length
) -> jax.Array:
    """Single-token attention against a (sequence-sharded) KV cache.

    Softmax over the sharded S axis lowers to partial max/sum + psum —
    the flash-decoding schedule — purely via SPMD propagation.
    """
    B, _, H, hd = q.shape
    S_max = k_cache.shape[1]
    KV = k_cache.shape[2]
    g = H // KV
    qg = q.reshape(B, KV, g, hd)
    scores = jnp.einsum("bkgh,bskh->bkgs", qg, k_cache).astype(jnp.float32)
    scores = scores / jnp.sqrt(hd).astype(jnp.float32)
    pos = jnp.arange(S_max)
    valid = pos[None, :] < jnp.reshape(length, (-1, 1))  # (B or 1, S)
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskh->bkgh", w.astype(v_cache.dtype), v_cache)
    return out.reshape(B, 1, H, hd)


# ---------------------------------------------------------------------------
# MoE (capacity-based dispatch; expert dim sharded over "model" = EP)
# ---------------------------------------------------------------------------


def moe_ffn(
    cfg: ModelConfig,
    x: jax.Array,  # (B, S, d)
    w_router: jax.Array,  # (d, E)
    w_gate: jax.Array,  # (E, d, f)
    w_up: jax.Array,  # (E, d, f)
    w_down: jax.Array,  # (E, f, d)
    shared: Optional[Tuple[jax.Array, jax.Array, jax.Array]] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Top-k capacity-bounded MoE. Returns (out, aux_loss)."""
    m: MoEConfig = cfg.moe
    B, S, d = x.shape
    T = B * S
    E, k = m.num_experts, m.top_k
    cap = max(1, int(T * k * m.capacity_factor / E))
    xt = x.reshape(T, d)

    logits = jnp.einsum("td,de->te", xt, w_router).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, k)  # (T, k)
    gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True), 1e-9)

    # position of each (token, choice) within its expert's capacity buffer
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)  # (T, k, E)
    flat = onehot.reshape(T * k, E)
    pos_in_expert = jnp.cumsum(flat, axis=0) * flat - 1  # (T*k, E)
    pos = pos_in_expert.max(axis=-1).reshape(T, k)  # (T, k)
    expert = idx
    keep = (pos < cap) & (pos >= 0)
    gate_vals = gate_vals * keep

    # dispatch: (E, cap, d)
    dispatch = jnp.zeros((E, cap, d), xt.dtype)
    tok_ids = jnp.broadcast_to(jnp.arange(T)[:, None], (T, k))
    dispatch = dispatch.at[expert, jnp.clip(pos, 0, cap - 1)].add(
        jnp.where(keep[..., None], xt[tok_ids], 0)
    )
    dispatch = shard_hint(dispatch, "model", None, None)

    h = jnp.einsum("ecd,edf->ecf", dispatch, use_weight(w_gate, "model", None, None))
    u = jnp.einsum("ecd,edf->ecf", dispatch, use_weight(w_up, "model", None, None))
    h = jax.nn.silu(h.astype(jnp.float32)).astype(xt.dtype) * u
    eo = jnp.einsum("ecf,efd->ecd", h, use_weight(w_down, "model", None, None))

    # combine
    gathered = eo[expert, jnp.clip(pos, 0, cap - 1)]  # (T, k, d)
    out = jnp.einsum("tk,tkd->td", gate_vals.astype(xt.dtype), gathered)

    if shared is not None:
        sg, su, sd = shared
        out = out + swiglu(xt[None], sg, su, sd)[0]

    # aux losses (load balance + router z) — standard formulations
    me = probs.mean(0)  # (E,)
    ce = (onehot.sum(1) > 0).astype(jnp.float32).mean(0)
    lb = E * jnp.sum(me * ce) * m.load_balance_loss
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2) * m.router_z_loss
    return out.reshape(B, S, d), lb + z
