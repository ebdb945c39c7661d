"""Pallas TPU kernels for the SkyByte tiering runtime's compute hot spots.

Each kernel package has:
  kernel.py — pl.pallas_call + BlockSpec TPU implementation
  ops.py    — dispatch wrapper. The served kernels (paged_attention,
              log_compact) take a ``mode``: "compiled" (TPU), "interpret"
              (the kernel body through the Pallas interpreter, for CPU
              tests) or "reference" (the jnp oracle)
  ref.py    — pure-jnp oracle

Kernels:
  paged_attention — decode attention over the paged HBM KV cache + the
                    token-granular write log (the paper's parallel
                    log+cache lookup, SIII-B read path)
  kv_log_append   — token append into the KV write-log ring (write path)
  log_compact     — newest-wins coalescing of log tokens into KV pages
                    (SIII-B log compaction)
  flash_attention — tiled causal attention for prefill (MXU-aligned)
"""

MODES = ("compiled", "interpret", "reference")


def pallas_interpret(mode: str) -> bool:
    """The ``interpret`` argument of a Pallas kernel run in ``mode``."""
    if mode not in MODES:
        raise ValueError(f"unknown kernel mode {mode!r}; known: {MODES}")
    return mode == "interpret"
