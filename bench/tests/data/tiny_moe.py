"""Plain reference of a small mixture-of-experts decoder, for the harness's
CPU tests: `dense_gqa.py`'s attention, and in place of its dense FFN a
dropless top-k expert layer, h2 = rmsnorm(x) * g_mlp:

    p = softmax(h2 W_router);  g = the top k of p, renormalised to sum 1
    x = x + sum_e g_e (silu(h2 Wg_e) * (h2 Wu_e)) Wd_e

Every expert runs on every position and the gates weight them (0 for an
expert not chosen), so no token is dropped. Beside `served_logits` it
gives the weights' leaf table (`shapes`) and a decoded token's FLOPs, in
which only the k chosen experts count.
"""
from __future__ import annotations

import functools
import importlib.util
import math
from pathlib import Path

import jax
import jax.numpy as jnp

from bench.weights import dense_shapes

_spec = importlib.util.spec_from_file_location(
    "dense_gqa", Path(__file__).with_name("dense_gqa.py"))
dense = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(dense)


def shapes(cfg: dict) -> dict:
    """The dense table with the FFN's leaves replaced by the program's
    expert leaves: router (L, d, E), we_gate/we_up (L, E, d, f), we_down
    (L, E, f, d)."""
    tree = dense_shapes(cfg)
    d, L = cfg["d_model"], cfg["n_layers"]
    E, f = cfg["moe"]["num_experts"], cfg["moe"]["d_ff_expert"]
    b = tree["blocks"]
    for k in ("w_gate", "w_up", "w_down"):
        del b[k]
    b.update(router=((L, d, E), 1 / math.sqrt(d)),
             we_gate=((L, E, d, f), 1 / math.sqrt(d)),
             we_up=((L, E, d, f), 1 / math.sqrt(d)),
             we_down=((L, E, f, d), 1 / math.sqrt(f)))
    return tree


def decode_token_flops(cfg: dict, context: int) -> int:
    """2 per weight a decoded token multiplies by (projections, router, the
    k chosen experts, unembedding), plus q.k and p.v over the context."""
    d, hd, H, KV = cfg["d_model"], cfg["head_dim"], cfg["n_heads"], cfg["n_kv_heads"]
    m = cfg["moe"]
    layer = (2 * d * H * hd + 2 * d * KV * hd + d * m["num_experts"]
             + m["top_k"] * 3 * d * m["d_ff_expert"])
    params = cfg["n_layers"] * layer + d * cfg["vocab"]
    return 2 * params + cfg["n_layers"] * 4 * H * hd * context


def _layer(cfg, control, x, p):
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
    x = dense.attention(cfg, control, x, p)
    h2 = dense._rms(x, p["mlp_norm"], cfg["norm_eps"])
    probs = jax.nn.softmax(dense._mm("sd,de->se", h2, p["router"], control), axis=-1)
    top, idx = jax.lax.top_k(probs, cfg["top_k"])
    gates = jnp.zeros_like(probs).at[jnp.arange(x.shape[0])[:, None], idx].set(
        top / jnp.sum(top, -1, keepdims=True))
    a = jax.nn.silu(dense._mm("sd,edf->esf", h2, p["we_gate"], control))
    u = dense._mm("sd,edf->esf", h2, p["we_up"], control)
    y = dense._mm("esf,efd->esd", a * u, p["we_down"], control)
    return x + jnp.einsum("se,esd->sd", gates, y, precision=dense.HI), None


@functools.partial(jax.jit, static_argnames=("cfg_items", "n_out", "control"))
def _logits(params, tokens, start, *, cfg_items, n_out, control):
    cfg = dict(cfg_items)
    x = params["embed"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(functools.partial(_layer, cfg, control), x, params["blocks"])
    h = jax.lax.dynamic_slice_in_dim(x, start, n_out, axis=0)
    h = dense._rms(h, params["final_norm"].astype(jnp.float32), cfg["norm_eps"])
    head = params["embed"].T if cfg["tie_embeddings"] else params["lm_head"]
    return dense._mm("sd,dv->sv", h, head.astype(jnp.float32), control)


def served_logits(cfg: dict, params, prompt, out, *, control=False, bucket=64):
    """Logits (len(out), V) that predict each served token `out[j]` from
    `prompt + out[:j]`, the sequence padded at its end to a multiple of
    `bucket`."""
    seq = list(prompt) + list(out[:-1])
    start, n_out = len(prompt) - 1, -(-len(out) // bucket) * bucket
    S = max(-(-len(seq) // bucket) * bucket, start + n_out)
    tokens = jnp.asarray(seq + [0] * (S - len(seq)), jnp.int32)
    keys = ("n_heads", "n_kv_heads", "head_dim", "norm_eps", "rope_theta",
            "tie_embeddings")
    items = tuple((k, cfg[k]) for k in keys) + (("top_k", cfg["moe"]["top_k"]),)
    logits = _logits(params, tokens, jnp.int32(start), cfg_items=items,
                     n_out=n_out, control=control)
    return logits[: len(out)]
