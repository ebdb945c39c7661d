"""Pallas TPU kernel: write-log compaction.

Grid = (F, L): one step per (flush target, layer). The target page is
merged in VMEM: start from the current page content, overlay every
matching log token at its in-page offset (newest-wins by slot order),
write back — ONE page-granular HBM write per flushed page, which is the
whole point of the paper's coalescing (vs one page write per token).
The log block rides in VMEM (the log is small by design: SkyByte sizes it
at 1/8 of SSD DRAM; here <=2MB so it fits VMEM comfortably).
flush target metadata rides in SMEM via scalar prefetch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(
    targets,  # (F, 3) SMEM: (request, logical_page, pool_slot)
    meta,  # (S, 2) SMEM
    logk_ref,  # (1, S, KV, hd)
    logv_ref,
    kp_in,  # (1, 1, page, KV, hd) current page content (gathered by index_map)
    vp_in,
    kp_out,  # (1, 1, page, KV, hd)
    vp_out,
    *,
    page: int,
    n_slots: int,
):
    f = pl.program_id(0)
    r = targets[f, 0]
    logical = targets[f, 1]

    kp_out[...] = kp_in[...]
    vp_out[...] = vp_in[...]

    def body(s, _):
        owner = meta[s, 0]
        lpos = meta[s, 1]
        match = (owner == r) & (r >= 0) & (lpos >= 0) & (lpos // page == logical)

        @pl.when(match)
        def _store():
            off = lpos % page
            kp_out[0, 0, pl.dslice(off, 1)] = logk_ref[0, pl.dslice(s, 1)].astype(
                kp_out.dtype
            )
            vp_out[0, 0, pl.dslice(off, 1)] = logv_ref[0, pl.dslice(s, 1)].astype(
                vp_out.dtype
            )

        return ()

    jax.lax.fori_loop(0, n_slots, body, ())


@functools.partial(jax.jit, static_argnames=("interpret",))
def log_compact_pallas(
    k_pages: jax.Array,  # (L, P, page, KV, hd)
    v_pages: jax.Array,
    log_k: jax.Array,  # (L, S, KV, hd)
    log_v: jax.Array,
    log_meta: jax.Array,  # (S, 2)
    flush_targets: jax.Array,  # (F, 3)
    *,
    interpret: bool = False,
):
    L, P, page, KV, hd = k_pages.shape
    S = log_k.shape[1]
    F = flush_targets.shape[0]

    def logmap(f, l, tg, mt):
        return (l, 0, 0, 0)

    def pagemap(f, l, tg, mt):
        return (l, jnp.maximum(tg[f, 2], 0), 0, 0, 0)

    kernel = functools.partial(_kernel, page=page, n_slots=S)
    # emit merged pages (F, L, page, KV, hd); scatter back outside (the
    # in-kernel aliased scatter would need dynamic output indexing)
    merged_k, merged_v = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(F, L),
            in_specs=[
                pl.BlockSpec((1, S, KV, hd), lambda f, l, tg, mt: (l, 0, 0, 0)),
                pl.BlockSpec((1, S, KV, hd), lambda f, l, tg, mt: (l, 0, 0, 0)),
                pl.BlockSpec((1, 1, page, KV, hd), pagemap),
                pl.BlockSpec((1, 1, page, KV, hd), pagemap),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, page, KV, hd), lambda f, l, tg, mt: (l, f, 0, 0, 0)),
                pl.BlockSpec((1, 1, page, KV, hd), lambda f, l, tg, mt: (l, f, 0, 0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((L, F, page, KV, hd), k_pages.dtype),
            jax.ShapeDtypeStruct((L, F, page, KV, hd), v_pages.dtype),
        ],
        interpret=interpret,
    )(flush_targets, log_meta, log_k, log_v, k_pages, v_pages)

    # scatter merged pages into the pool; padding rows (request or slot -1)
    # aim past the pool and are dropped, so they never race a real row
    slots = flush_targets[:, 2]
    valid = (flush_targets[:, 0] >= 0) & (slots >= 0)
    dest = jnp.where(valid, slots, P)
    k_pages = k_pages.at[:, dest].set(merged_k, mode="drop")
    v_pages = v_pages.at[:, dest].set(merged_v, mode="drop")
    return k_pages, v_pages
