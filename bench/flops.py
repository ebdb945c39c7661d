"""Operations and bytes the served work needs, from shapes alone.

`cfg` is a configuration file's dict (bench/configs/<name>.json).
"""
from __future__ import annotations

BF16 = 2


def matmul_params(cfg: dict) -> int:
    """Weights a decoded token multiplies by: every layer's projections and
    FFN, and the unembedding. The embedding lookup is a gather, not a matmul."""
    d, hd = cfg["d_model"], cfg["head_dim"]
    H, KV, F = cfg["n_heads"], cfg["n_kv_heads"], cfg["d_ff"]
    layer = d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * F
    return cfg["n_layers"] * layer + d * cfg["vocab"]


def decode_token_flops(cfg: dict, context: int) -> int:
    """Model FLOPs of one decoded token that attends `context` positions
    (its own included): 2 per weight, plus q.k and p.v over the context."""
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
