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
                            pages are not HBM-resident and runs others

The device holds data only, a flat dict of six arrays: each tier's K/V
pools and the write log. Every index into them lives on the host and
reaches a program as an argument; policy (promotion targets, flush targets,
scheduling) is host-side too, as the paper splits FTL policy (firmware)
from the data path (hardware).

The kernels' path is chosen from the platform in one place,
``kernel_mode``: compiled Pallas kernels on a TPU, the jnp reference
everywhere else.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

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
        "log_k": jnp.zeros((L, c.log_slots, KV, hd), dtype),
        "log_v": jnp.zeros((L, c.log_slots, KV, hd), dtype),
    }


def kernel_mode() -> str:
    """How the served path runs its kernels: "compiled" Pallas kernels on a
    TPU, the jnp "reference" on any other backend. Tests that need the
    kernel bodies on the CPU patch this to return "interpret"."""
    return "compiled" if jax.default_backend() == "tpu" else "reference"


def host_slot(kv_cfg: TieredKVConfig, req: int, logical: int) -> int:
    """Backing-store slot for a request's logical page (direct-mapped)."""
    return req * kv_cfg.max_pages_per_req + logical


# Columns of the decode step's batch rows (``batch_rows``); the page-table
# row fills the columns from TABLE on.
TOKEN, REQ, LENGTH, MARK, SLOT, TABLE = range(6)


def batch_rows(kv_cfg: TieredKVConfig, tokens, req_ids: np.ndarray,
               lengths: np.ndarray, compacted: np.ndarray,
               page_table: np.ndarray, log_tail: int) -> np.ndarray:
    """The decode step's input, one int32 row per batch slot: token, request
    id, length (the position it writes), watermark, log slot (consecutive
    from ``log_tail``) and page-table row. Padding rows (past ``req_ids``)
    hold request -1, length and watermark 0 and request 0's table row."""
    n = len(req_ids)
    rows = np.zeros((kv_cfg.batch, TABLE + kv_cfg.max_pages_per_req), np.int32)
    rows[:, REQ] = -1
    rows[:n, TOKEN] = tokens
    rows[:n, REQ] = req_ids
    rows[:n, LENGTH] = lengths[req_ids]
    rows[:n, MARK] = compacted[req_ids]
    rows[:, SLOT] = log_tail + np.arange(kv_cfg.batch)
    rows[:, TABLE:] = page_table[np.maximum(rows[:, REQ], 0)]
    return rows


# ---------------------------------------------------------------------------
# device ops
# ---------------------------------------------------------------------------


def promote_pages(hbm_k, hbm_v, host_k, host_v, pairs: jax.Array, n: jax.Array):
    """Copy the first ``n`` rows of ``pairs`` ((F, 2) int32: host slot, HBM
    slot) from the host-tier pools into the HBM pools, one page at a time;
    rows from ``n`` on are padding and write nothing. Models the
    page-granular host->HBM DMA. Jitted with the HBM pools donated, the
    copy is done in place: it moves the pages it promotes and allocates no
    pool."""

    def copy_one(i, pools):
        src, dst = pairs[i, 0], pairs[i, 1]
        return tuple(
            jax.lax.dynamic_update_slice_in_dim(
                pool, jax.lax.dynamic_slice_in_dim(host, src, 1, axis=1), dst, axis=1)
            for pool, host in zip(pools, (host_k, host_v))
        )

    return jax.lax.fori_loop(0, n, copy_one, (hbm_k, hbm_v))


def write_prefill_pages(kv_cfg: TieredKVConfig, state, req: int, k, v):
    """Scatter a dense prefill cache (L, S, KV, hd) into the request's
    host-tier pages (the paper's initial placement: data starts in the
    slow tier). Writes only the host-tier pools."""
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
    return state


def build_paged_decode_step(spec: ModelSpec, kv_cfg: TieredKVConfig):
    """Decode step over the tiered KV state for GQA decoder families
    (dense/moe/vlm). Returns step(params, state, rows, log_meta) ->
    (next_tokens (batch, 1), log_k, log_v): ``rows`` from ``batch_rows``,
    ``log_meta`` the host's log index ((log_slots, 2) int32 owner and
    position, -1 where empty) with this step's rows in it. The HBM pools
    are read, never returned, and no index is computed: the host owns them.

    The current token's K/V is appended to the write log (token-granular,
    no page read-modify-write — the paper's write path) and the attention
    reads pages + log in parallel (the paper's read path).
    """
    cfg = spec.cfg
    mode = kernel_mode()

    def step(params, state, rows, log_meta):
        B = rows.shape[0]
        req_ids, lengths = rows[:, REQ], rows[:, LENGTH]
        page_table = rows[:, TABLE:]  # (B, N)
        tail = rows[0, SLOT]  # the rows' slots are consecutive

        x = jnp.take(params["embed"], rows[:, TOKEN:TOKEN + 1], axis=0)  # (B, 1, d)
        positions = lengths[:, None]

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
                page_lengths=rows[:, MARK], req_ids=req_ids, mode=mode,
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
        return next_tok, log_k, log_v

    return step


def compact_log(hbm_k, hbm_v, host_k, host_v, log_k, log_v, log_meta,
                flush_hbm: jax.Array, flush_host: jax.Array):
    """Run log compaction into both pools; returns the four pools.

    log_meta: the host's log index ((log_slots, 2) int32: owner request and
    position, -1 where empty), which masks the log's stale rows.
    flush_hbm / flush_host: (F, 3) int32 (request, logical_page, pool_slot)
    built by the engine from that index (unique dirty pages — the paper's
    first-level hash-table scan); rows with request -1 are padding."""
    mode = kernel_mode()
    hbm_k, hbm_v = log_compact(
        hbm_k, hbm_v, log_k, log_v, log_meta, flush_hbm, mode=mode)
    # one flush's merged pages live at a time: unordered, XLA may run both
    # merges before either scatter, tripling the temporary memory
    hbm_k, hbm_v, log_k, log_v = jax.lax.optimization_barrier(
        (hbm_k, hbm_v, log_k, log_v))
    host_k, host_v = log_compact(
        host_k, host_v, log_k, log_v, log_meta, flush_host, mode=mode)
    return hbm_k, hbm_v, host_k, host_v
