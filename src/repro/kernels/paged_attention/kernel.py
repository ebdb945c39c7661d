"""Pallas TPU kernel: decode attention over a paged KV pool.

Flash-decoding schedule: grid = (B, N_pages); the page axis is the
sequential minor-most grid dimension, so the online-softmax state
(m, l, acc) lives in VMEM scratch and is carried across page steps.
The page table and lengths ride in SMEM via PrefetchScalarGridSpec, and
each k/v page block is streamed HBM->VMEM by the BlockSpec index_map
*through the page table* — non-resident pages (slot -1) are masked, never
fetched twice (the paper's MSHR-free parallel lookup, adapted: the page
table here plays the role of SkyByte's two-level index).

Block shapes: one step fetches a whole page across all KV heads. The pool
(P, page, KV, hd) is viewed as (P, page*KV, hd) (a free row-major
reshape), so a K/V block is (page*KV, hd) — its last two dims equal the
array's, which TPU tiling accepts for any KV (a (page, 1, hd) block over
the KV axis does not). GQA is handled inside the kernel: all H query rows
score against all page*KV rows in one matmul, and each query row keeps
only the columns of its own KV head (fp32 accumulation).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(
    # scalar prefetch
    page_table,  # (B, N) int32 in SMEM
    lengths,  # (B,) int32 in SMEM
    # blocks
    q_ref,  # (1, H, hd)
    k_ref,  # (1, page*KV, hd) row j = token j // KV of kv head j % KV
    v_ref,  # (1, page*KV, hd)
    tok_ref,  # (1, page*KV) int32: in-page token offset of row j
    grp_ref,  # (H, page*KV) int32: 1 where query head h reads kv head j % KV
    out_ref,  # (1, H, hd)
    m_ref,  # (1, H, 1) fp32 running max (output)
    l_ref,  # (1, H, 1) fp32 running denom (output)
    # scratch
    acc,  # (H, hd) fp32
    m_scr,  # (H, 1) fp32
    l_scr,  # (H, 1) fp32
    *,
    page: int,
    n_pages: int,
):
    b = pl.program_id(0)
    n = pl.program_id(1)

    @pl.when(n == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    q = q_ref[0].astype(jnp.float32)  # (H, hd)
    k = k_ref[0].astype(jnp.float32)  # (page*KV, hd)
    v = v_ref[0].astype(jnp.float32)
    hd = q.shape[-1]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) / jnp.sqrt(1.0 * hd)  # (H, page*KV)

    pos = n * page + tok_ref[...]  # (1, page*KV)
    resident = page_table[b, n] >= 0
    valid = (grp_ref[...] != 0) & (pos < lengths[b]) & resident
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]  # (H, 1)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.where(valid, jnp.exp(s - m_cur), 0.0)  # (H, page*KV)
    l_cur = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc[...] = acc[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_scr[...] = m_cur
    l_scr[...] = l_cur

    @pl.when(n == n_pages - 1)
    def _done():
        denom = jnp.maximum(l_scr[...], 1e-30)
        out_ref[0] = (acc[...] / denom).astype(out_ref.dtype)
        m_ref[0] = m_scr[...]
        l_ref[0] = l_scr[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_pallas(
    q: jax.Array,  # (B, H, hd)
    k_pages: jax.Array,  # (P, page, KV, hd)
    v_pages: jax.Array,
    page_table: jax.Array,  # (B, N) int32
    lengths: jax.Array,  # (B,) int32
    *,
    interpret: bool = False,
):
    """Returns (out (B, H, hd), m (B, KV, g, 1), l (B, KV, g, 1))."""
    B, H, hd = q.shape
    P, page, KV, _ = k_pages.shape
    N = page_table.shape[1]
    g = H // KV
    PK = page * KV
    k_flat = k_pages.reshape(P, PK, hd)
    v_flat = v_pages.reshape(P, PK, hd)
    col = jnp.arange(PK, dtype=jnp.int32)
    tok = (col // KV)[None]  # (1, PK)
    grp = (jnp.arange(H, dtype=jnp.int32)[:, None] // g == (col % KV)[None])
    grp = grp.astype(jnp.int32)  # (H, PK)

    def qmap(b, n, pt, ln):
        return (b, 0, 0)

    def kvmap(b, n, pt, ln):
        return (jnp.maximum(pt[b, n], 0), 0, 0)

    def constmap(b, n, pt, ln):
        return (0, 0)

    kernel = functools.partial(_kernel, page=page, n_pages=N)
    out, m, l = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, N),
            in_specs=[
                pl.BlockSpec((1, H, hd), qmap),
                pl.BlockSpec((1, PK, hd), kvmap),
                pl.BlockSpec((1, PK, hd), kvmap),
                pl.BlockSpec((1, PK), constmap),
                pl.BlockSpec((H, PK), constmap),
            ],
            out_specs=[
                pl.BlockSpec((1, H, hd), qmap),
                pl.BlockSpec((1, H, 1), qmap),
                pl.BlockSpec((1, H, 1), qmap),
            ],
            scratch_shapes=[
                pltpu.VMEM((H, hd), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, H, hd), q.dtype),
            jax.ShapeDtypeStruct((B, H, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, H, 1), jnp.float32),
        ],
        interpret=interpret,
    )(page_table, lengths, q, k_flat, v_flat, tok, grp)
    return out, m.reshape(B, KV, g, 1), l.reshape(B, KV, g, 1)
