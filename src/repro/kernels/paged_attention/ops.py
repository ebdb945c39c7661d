"""Dispatch wrapper: paged decode attention (+ optional write-log merge).

The Pallas kernel covers the page pool; the (small) write log is attended
with a jnp pass and merged via the standard flash-decoding (m, l)
combination — numerically identical to attending the concatenation, and it
keeps the log's irregular (request-interleaved) layout out of the kernel's
tiling. Runtime invariant (append-only KV): a logical position lives in
EITHER the log or a page, never both, so the merge needs no shadowing.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import pallas_interpret
from repro.kernels.paged_attention.kernel import paged_decode_attention_pallas
from repro.kernels.paged_attention.ref import paged_decode_attention_ref

NEG_INF = -1e30


def _log_attention(q, log_k, log_v, log_meta, lengths, req_ids):
    """jnp attention over the write-log ring. Returns (out, m, l)."""
    B, H, hd = q.shape
    S, KV, _ = log_k.shape
    g = H // KV
    qg = q.reshape(B, KV, g, hd).astype(jnp.float32)
    k = log_k.astype(jnp.float32)
    scores = jnp.einsum("bkgh,skh->bkgs", qg, k) / jnp.sqrt(1.0 * hd)
    owner, lpos = log_meta[:, 0], log_meta[:, 1]
    valid = (owner[None] == req_ids[:, None]) & (owner[None] >= 0) & (
        req_ids[:, None] >= 0
    )
    valid = valid & (lpos[None] < lengths[:, None]) & (lpos[None] >= 0)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - m)
    p = jnp.where(valid[:, None, None, :], p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bkgs,skh->bkgh", p, log_v.astype(jnp.float32))
    return out, m, l  # out is UN-normalized (sum of p*v)


def paged_decode_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_table: jax.Array,
    lengths: jax.Array,
    log_k: Optional[jax.Array] = None,
    log_v: Optional[jax.Array] = None,
    log_meta: Optional[jax.Array] = None,
    page_lengths: Optional[jax.Array] = None,
    req_ids: Optional[jax.Array] = None,
    *,
    mode: str,
) -> jax.Array:
    """(B, H, hd) attention output over pages (+ log).

    ``mode``: "compiled" runs the Pallas kernel compiled for the TPU,
    "interpret" runs it through the Pallas interpreter, "reference" runs
    the jnp oracle.

    ``page_lengths`` (default = lengths): per-request compaction watermark —
    page entries are valid only below it; positions at/above it live in the
    write log. This is the disjointness invariant the runtime maintains
    (the paper's "log holds the newest data until compaction").
    ``req_ids`` (default arange(B)): the request each batch row serves —
    log entries are owned by request id, not batch position.
    """
    if page_lengths is None:
        page_lengths = lengths
    if req_ids is None:
        req_ids = jnp.arange(q.shape[0], dtype=jnp.int32)
    if mode == "reference":
        return paged_decode_attention_ref(
            q, k_pages, v_pages, page_table, lengths, log_k, log_v, log_meta,
            page_lengths=page_lengths, req_ids=req_ids,
        )
    B, H, hd = q.shape
    KV = k_pages.shape[2]
    g = H // KV
    out_p, m_p, l_p = paged_decode_attention_pallas(
        q, k_pages, v_pages, page_table, page_lengths,
        interpret=pallas_interpret(mode),
    )
    if log_k is None:
        return out_p
    out_l, m_l, l_l = _log_attention(q, log_k, log_v, log_meta, lengths, req_ids)
    # flash-decoding combine: pages output is normalized, log's is not
    out_pg = out_p.reshape(B, KV, g, hd).astype(jnp.float32)
    m = jnp.maximum(m_p, m_l)
    a_p = jnp.exp(m_p - m) * l_p
    a_l = jnp.exp(m_l - m)
    denom = a_p + a_l * l_l
    denom = jnp.maximum(denom, 1e-30)
    out = (out_pg * a_p + out_l * a_l) / denom
    return out.reshape(B, H, hd).astype(q.dtype)
