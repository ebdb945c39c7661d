"""SkyByte tiering runtime for TPU serving (DESIGN.md §2 Layer B).

The paper's memory system, re-expressed for an LLM serving engine:

  flash chips            -> host-tier page pool (big, slow to reach)
  SSD DRAM data cache    -> HBM page pool (fast, small)
  cacheline write log    -> token-granular KV write-log ring in HBM
  log compaction         -> kernels/log_compact: newest-wins coalescing of
                            log tokens into page-granular pool writes
  page-granular flash IO -> page-granular host<->HBM copies
  adaptive migration     -> hot-page promotion into the HBM pool (engine
                            policy; LRU eviction under pressure)
  coordinated ctx switch -> the serving scheduler parks requests whose
                            pages are not HBM-resident (predicted-slow,
                            Algorithm-1-style estimate) and runs others

All device state is a flat dict of fixed-shape arrays (jit/pjit friendly);
policy (promotion targets, flush targets, scheduling) is host-side, exactly
as the paper splits FTL policy (firmware) from the data path (hardware).

The kernels' path is chosen from the platform in one place,
``kernel_mode``: compiled Pallas kernels on a TPU, the jnp reference
everywhere else.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.log_compact.ops import log_compact
from repro.kernels.paged_attention.ops import paged_decode_attention
from repro.models.api import ModelSpec
from repro.models.dense import _attn_params, _ffn, unembed
from repro.models.layers import project_qkv, rmsnorm

Pytree = Any


@dataclasses.dataclass(frozen=True)
class TieredKVConfig:
    page_size: int = 16
    n_hbm_pages: int = 32  # HBM pool slots (the "SSD DRAM cache")
    max_requests: int = 8
    max_pages_per_req: int = 8
    log_slots: int = 64
    batch: int = 4  # decode batch width (scheduled requests per step)
    promote_pages_per_step: int = 4  # host->HBM copy budget per step
    fetch_page_us: float = 50.0  # per-page host->HBM latency estimate
    park_threshold_us: float = 50.0  # Algorithm-1-style switch threshold

    @property
    def n_host_pages(self) -> int:
        return self.max_requests * self.max_pages_per_req


def init_state(
    kv_cfg: TieredKVConfig, cfg: ModelConfig, dtype=jnp.float32
) -> Dict[str, jax.Array]:
    L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.resolved_head_dim
    c = kv_cfg
    shape_pool = (L, c.n_hbm_pages, c.page_size, KV, hd)
    shape_host = (L, c.n_host_pages, c.page_size, KV, hd)
    return {
        "hbm_k": jnp.zeros(shape_pool, dtype),
        "hbm_v": jnp.zeros(shape_pool, dtype),
        "host_k": jnp.zeros(shape_host, dtype),
        "host_v": jnp.zeros(shape_host, dtype),
        "page_table": -jnp.ones((c.max_requests, c.max_pages_per_req), jnp.int32),
        "log_k": jnp.zeros((L, c.log_slots, KV, hd), dtype),
        "log_v": jnp.zeros((L, c.log_slots, KV, hd), dtype),
        "log_meta": -jnp.ones((c.log_slots, 2), jnp.int32),
        "log_tail": jnp.zeros((), jnp.int32),
        "lengths": jnp.zeros((c.max_requests,), jnp.int32),
        # compaction watermark: positions < compacted live in pages;
        # positions >= compacted live in the write log (disjointness)
        "compacted": jnp.zeros((c.max_requests,), jnp.int32),
    }


def kernel_mode() -> str:
    """How the served path runs its kernels: "compiled" Pallas kernels on a
    TPU, the jnp "reference" on any other backend. Tests that need the
    kernel bodies on the CPU patch this to return "interpret"."""
    return "compiled" if jax.default_backend() == "tpu" else "reference"


def host_slot(kv_cfg: TieredKVConfig, req: int, logical: int) -> int:
    """Backing-store slot for a request's logical page (direct-mapped)."""
    return req * kv_cfg.max_pages_per_req + logical


# ---------------------------------------------------------------------------
# device ops
# ---------------------------------------------------------------------------


def copy_pages(dst_k, dst_v, src_k, src_v, pairs: jax.Array):
    """Copy pages src->dst pool. pairs: (F, 2) int32 (src_slot, dst_slot),
    -1 rows ignored. Models the page-granular host<->HBM DMA."""
    src, dst = pairs[:, 0], pairs[:, 1]
    valid = (src >= 0) & (dst >= 0)
    ssafe = jnp.maximum(src, 0)
    dsafe = jnp.maximum(dst, 0)
    cur_k = dst_k[:, dsafe]
    cur_v = dst_v[:, dsafe]
    new_k = jnp.where(valid[None, :, None, None, None], src_k[:, ssafe], cur_k)
    new_v = jnp.where(valid[None, :, None, None, None], src_v[:, ssafe], cur_v)
    return dst_k.at[:, dsafe].set(new_k), dst_v.at[:, dsafe].set(new_v)


def write_prefill_pages(kv_cfg: TieredKVConfig, state, req: int, k, v):
    """Scatter a dense prefill cache (L, S, KV, hd) into the request's
    host-tier pages (the paper's initial placement: data starts in the
    slow tier)."""
    L, S, KV, hd = k.shape
    p = kv_cfg.page_size
    n = (S + p - 1) // p
    pad = n * p - S
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    pages_k = k.reshape(L, n, p, KV, hd)
    pages_v = v.reshape(L, n, p, KV, hd)
    base = host_slot(kv_cfg, req, 0)
    state = dict(state)
    state["host_k"] = jax.lax.dynamic_update_slice_in_dim(
        state["host_k"], pages_k.astype(state["host_k"].dtype), base, axis=1
    )
    state["host_v"] = jax.lax.dynamic_update_slice_in_dim(
        state["host_v"], pages_v.astype(state["host_v"].dtype), base, axis=1
    )
    state["lengths"] = state["lengths"].at[req].set(S)
    state["compacted"] = state["compacted"].at[req].set(S)
    return state


def build_paged_decode_step(spec: ModelSpec, kv_cfg: TieredKVConfig):
    """Decode step over the tiered KV state for GQA decoder families
    (dense/moe/vlm). Returns step(params, state, tokens, req_ids) ->
    (next_tokens, updates), where ``updates`` holds only the state entries
    the step writes (the log, its metadata and the lengths); the page pools
    are read, never returned, so a step copies none of them.

    The current token's K/V is appended to the write log (token-granular,
    no page read-modify-write — the paper's write path) and the attention
    reads pages + log in parallel (the paper's read path).
    """
    cfg = spec.cfg
    mode = kernel_mode()

    def step(params, state, tokens, req_ids):
        B = tokens.shape[0]
        safe_req = jnp.maximum(req_ids, 0)
        lengths = jnp.where(req_ids >= 0, state["lengths"][safe_req], 0)  # (B,)
        compacted = jnp.where(req_ids >= 0, state["compacted"][safe_req], 0)
        page_table = state["page_table"][safe_req]  # (B, N)

        x = jnp.take(params["embed"], tokens, axis=0)  # (B, 1, d)
        positions = lengths[:, None]

        tail = state["log_tail"]
        meta_rows = jnp.stack(
            [req_ids, jnp.where(req_ids >= 0, lengths, -1)], axis=-1
        )
        log_meta = jax.lax.dynamic_update_slice_in_dim(
            state["log_meta"], meta_rows, tail, axis=0
        )

        def body(x, xs):
            p_l, hbm_k_l, hbm_v_l, log_k_l, log_v_l = xs
            h = rmsnorm(x, p_l["attn_norm"], cfg.norm_eps)
            q, k, v = project_qkv(cfg, _attn_params(cfg, p_l), h, positions)
            # write path: append this token's KV to the log (per layer)
            log_k_l = jax.lax.dynamic_update_slice_in_dim(
                log_k_l, k[:, 0].astype(log_k_l.dtype), tail, axis=0
            )
            log_v_l = jax.lax.dynamic_update_slice_in_dim(
                log_v_l, v[:, 0].astype(log_v_l.dtype), tail, axis=0
            )
            # read path: pages + log in parallel (lengths+1 covers the
            # just-appended token)
            o = paged_decode_attention(
                q[:, 0], hbm_k_l, hbm_v_l, page_table, lengths + 1,
                log_k_l, log_v_l, log_meta,
                page_lengths=compacted, req_ids=req_ids, mode=mode,
            )
            x2 = x + jnp.einsum("bh,hd->bd", o.reshape(B, -1), p_l["wo"])[:, None]
            h2 = rmsnorm(x2, p_l["mlp_norm"], cfg.norm_eps)
            f, _ = _ffn(cfg, p_l, h2)
            return x2 + f, (log_k_l, log_v_l)

        x, (log_k, log_v) = jax.lax.scan(
            body, x,
            (params["blocks"], state["hbm_k"], state["hbm_v"],
             state["log_k"], state["log_v"]),
        )
        logits = unembed(cfg, params, x)[:, 0]
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]

        updates = {
            "log_k": log_k,
            "log_v": log_v,
            "log_meta": log_meta,
            "log_tail": tail + B,
            "lengths": state["lengths"].at[safe_req].add(
                (req_ids >= 0).astype(jnp.int32)
            ),
        }
        return next_tok, updates

    return step


def compact_log(
    kv_cfg: TieredKVConfig, state, flush_hbm: jax.Array, flush_host: jax.Array
):
    """Run log compaction into both pools and clear the log.

    flush_hbm / flush_host: (F, 3) int32 (request, logical_page, pool_slot)
    built by the engine from log_meta (unique dirty pages — the paper's
    first-level hash-table scan); rows with request -1 are padding."""
    mode = kernel_mode()
    state = dict(state)
    state["hbm_k"], state["hbm_v"] = log_compact(
        state["hbm_k"], state["hbm_v"], state["log_k"], state["log_v"],
        state["log_meta"], flush_hbm, mode=mode,
    )
    state["host_k"], state["host_v"] = log_compact(
        state["host_k"], state["host_v"], state["log_k"], state["log_v"],
        state["log_meta"], flush_host, mode=mode,
    )
    state["log_meta"] = -jnp.ones_like(state["log_meta"])
    state["log_tail"] = jnp.zeros((), jnp.int32)
    # everything logged so far is now in pages
    state["compacted"] = state["lengths"]
    return state
