"""Dispatch wrapper for log compaction."""
from __future__ import annotations

from repro.kernels import pallas_interpret
from repro.kernels.log_compact.kernel import log_compact_pallas
from repro.kernels.log_compact.ref import log_compact_ref


def log_compact(
    k_pages, v_pages, log_k, log_v, log_meta, flush_targets, *, mode: str,
):
    """``mode`` as for ``paged_decode_attention``: "compiled", "interpret"
    or "reference"."""
    if mode == "reference":
        return log_compact_ref(k_pages, v_pages, log_k, log_v, log_meta, flush_targets)
    return log_compact_pallas(
        k_pages, v_pages, log_k, log_v, log_meta, flush_targets,
        interpret=pallas_interpret(mode),
    )
