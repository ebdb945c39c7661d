"""Seeded random weights, made on the device in one jitted call.

The served program and the plain reference (`bench/reference/`) both take
their weights from `make`, so the reference needs nothing the program made:
it calls `make` again with the same seed. `make` takes the configuration
file's dict. Its table of leaves comes from `shapes(config)` in the
configuration's reference module (`bench/refs.py`) where that module
defines one, and otherwise from `dense_shapes`, a dense GQA decoder. Either
table follows the layout the program's `ModelSpec` serves (stacked
per-layer leaves under "blocks"); the leaves draw from one key stream in
the order of the flattened table.
"""
from __future__ import annotations

import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bench import refs


def key_for(seed: int, stream: int) -> jax.Array:
    """A PRNG key from a seed of any size (the benchmark's seeds pass 2**32)."""
    words = np.random.SeedSequence([seed % 2**64, stream]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def dense_shapes(cfg: dict) -> dict:
    """(shape, scale or "gain") for every leaf. Matrices draw N(0, 1/fan_in);
    the embedding (and untied head) N(0, 0.02^2); biases N(0, 0.02^2), so
    the bias path is exercised; norm gains 1 + N(0, 0.1^2)."""
    d, L, hd = cfg["d_model"], cfg["n_layers"], cfg["head_dim"]
    H, KV, F, V = cfg["n_heads"], cfg["n_kv_heads"], cfg["d_ff"], cfg["vocab"]
    blocks = {
        "attn_norm": ((L, d), "gain"),
        "wq": ((L, d, H * hd), 1 / math.sqrt(d)),
        "wk": ((L, d, KV * hd), 1 / math.sqrt(d)),
        "wv": ((L, d, KV * hd), 1 / math.sqrt(d)),
        "wo": ((L, H * hd, d), 1 / math.sqrt(H * hd)),
        "mlp_norm": ((L, d), "gain"),
        "w_gate": ((L, d, F), 1 / math.sqrt(d)),
        "w_up": ((L, d, F), 1 / math.sqrt(d)),
        "w_down": ((L, F, d), 1 / math.sqrt(F)),
    }
    if cfg["qkv_bias"]:
        blocks.update(bq=((L, H * hd), 0.02), bk=((L, KV * hd), 0.02),
                      bv=((L, KV * hd), 0.02))
    if cfg["qk_norm"]:
        blocks.update(q_norm=((L, hd), "gain"), k_norm=((L, hd), "gain"))
    tree = {"embed": ((V, d), 0.02), "final_norm": ((d,), "gain"),
            "blocks": blocks}
    if not cfg["tie_embeddings"]:
        tree["lm_head"] = ((d, V), 0.02)
    return tree


def _draw(key, shape, scale, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    if scale == "gain":
        return (1.0 + 0.1 * x).astype(dtype)
    return (scale * x).astype(dtype)


def make(config: dict, seed: int, dtype=jnp.bfloat16, *, root: Path = refs.ROOT) -> dict:
    """All weights in one jitted call on the default device, in `dtype`.
    `root` is the checkout whose `bench/reference/` holds the configuration's
    reference module."""
    spec = (refs.own(root, config, "shapes") or dense_shapes)(config)
    leaves, treedef = jax.tree_util.tree_flatten(
        spec, is_leaf=lambda x: isinstance(x, tuple))

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree_util.tree_unflatten(
            treedef, [_draw(k, s, sc, dtype) for k, (s, sc) in zip(keys, leaves)])

    return build(key_for(seed, 0))
