"""Operations and bytes the served work needs, from shapes alone.

`cfg` is a configuration file's dict (bench/configs/<name>.json). The two
counts the readers use, `decode_token_flops` and `paged_attn_work`, come
from the configuration's reference module where it defines a function of
that name (bench/refs.py), so that a configuration whose layers differ
(experts, windows) counts its own work; otherwise from the dense GQA
decoder's formulas here.
"""
from __future__ import annotations

from pathlib import Path

from bench import refs

BF16 = 2


def matmul_params(cfg: dict) -> int:
    """Weights a decoded token multiplies by: every layer's projections and
    FFN, and the unembedding. The embedding lookup is a gather, not a matmul."""
    d, hd = cfg["d_model"], cfg["head_dim"]
    H, KV, F = cfg["n_heads"], cfg["n_kv_heads"], cfg["d_ff"]
    layer = d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * F
    return cfg["n_layers"] * layer + d * cfg["vocab"]


def decode_token_flops(cfg: dict, context: int, *, root: Path = refs.ROOT) -> int:
    """Model FLOPs of one decoded token that attends `context` positions
    (its own included). Dense: 2 per weight, plus q.k and p.v over the
    context in every layer."""
    own = refs.own(root, cfg, "decode_token_flops")
    if own:
        return own(cfg, context)
    attn = 2 * 2 * cfg["n_heads"] * cfg["head_dim"] * context
    return 2 * matmul_params(cfg) + cfg["n_layers"] * attn


def paged_pages_lower_bound(context: int, page: int, log_slots: int,
                            batch: int) -> int:
    """Fewest pool pages the paged kernel must read for a token attending
    `context` positions. Positions at or above the compaction watermark are
    in the write log, not in pages; a request holds at most
    `log_slots // batch - 1` log entries before its step appends one more,
    so the watermark is at least context - log_slots // batch."""
    paged = max(0, context - log_slots // batch)
    return -(-paged // page)


def paged_attn_bytes(cfg: dict, pages_per_row: list, page: int) -> int:
    """Bytes one decode step's paged-attention calls need over all layers:
    K and V of each scheduled row's valid pages, plus its q and its output."""
    L, KV, H, hd = cfg["n_layers"], cfg["n_kv_heads"], cfg["n_heads"], cfg["head_dim"]
    kv = sum(pages_per_row) * page * KV * hd * BF16 * 2
    qo = len(pages_per_row) * H * hd * BF16 * 2
    return L * (kv + qo)


def paged_attn_work(cfg: dict, contexts: list, engine: dict, *,
                    root: Path = refs.ROOT) -> tuple:
    """(bytes, FLOPs) of one decode step's paged-attention calls, whose rows
    attend `contexts` positions, under a mix's `engine` settings. Dense: each
    row's pages counted from below (`paged_pages_lower_bound`), their bytes
    by `paged_attn_bytes`, and q.k and p.v over those pages in every layer."""
    own = refs.own(root, cfg, "paged_attn_work")
    if own:
        return own(cfg, contexts, engine)
    page = engine["page_size"]
    pages = [paged_pages_lower_bound(c, page, engine["log_slots"], engine["batch"])
             for c in contexts]
    flops = cfg["n_layers"] * sum(4 * cfg["n_heads"] * cfg["head_dim"] * p * page
                                  for p in pages)
    return paged_attn_bytes(cfg, pages, page), flops
