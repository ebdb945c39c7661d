"""Plain reference of a dense GQA decoder (Qwen2/Qwen3 layer equations).

Straight `jax.numpy` in float32 with matmuls at `HIGHEST` precision: no
kernels, no cache, no batching across requests. It imports nothing of the
program. Per layer (pre-norm):

    h = rmsnorm(x) * g_attn
    q, k, v = h Wq (+ bq), h Wk (+ bk), h Wv (+ bv)      (GQA: KV < H heads)
    q, k = rmsnorm(q) * g_q, rmsnorm(k) * g_k            (qk-norm, Qwen3 only)
    q, k = rope(q), rope(k)                              (rotate-half, theta)
    x = x + softmax(q k^T / sqrt(hd), causal) v Wo
    x = x + (silu(h2 Wg) * (h2 Wu)) Wd,  h2 = rmsnorm(x) * g_mlp
    logits = (rmsnorm(x) * g_final) E^T  (tied)  or  ... W_head

`control=True` computes the same equations with every matmul's operands
rounded to float8 e4m3 (scaled per tensor, per row for activations), the
precision step below the bfloat16 the configurations state: the benchmark's
control, which its correctness limit has to reject.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _fp8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / E4M3_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec, a, b, control):
    """einsum in float32; with `control`, operands rounded to fp8 first
    (activations per row: the contracted axis; weights per tensor)."""
    if control:
        a = _fp8(a, axis=-1)
        b = _fp8(b, axis=None)
    return jnp.einsum(spec, a, b, precision=HI)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x (S, heads, hd); rotate-half convention."""
    S, _, hd = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(cfg, control, x, p):
    """x plus the attention block's output; `p` holds one layer's leaves in
    float32."""
    S = x.shape[0]
    H, KV, hd, eps = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"], cfg["norm_eps"]
    h = _rms(x, p["attn_norm"], eps)
    q = _mm("sd,dh->sh", h, p["wq"], control)
    k = _mm("sd,dh->sh", h, p["wk"], control)
    v = _mm("sd,dh->sh", h, p["wv"], control)
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q, k, v = q.reshape(S, H, hd), k.reshape(S, KV, hd), v.reshape(S, KV, hd)
    if "q_norm" in p:
        q, k = _rms(q, p["q_norm"], eps), _rms(k, p["k_norm"], eps)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    g = H // KV
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)  # (S, H, hd)
    scores = _mm("qhd,khd->hqk", q, k, control) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((S, S), bool))
    w = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = _mm("hqk,khd->qhd", w, v, control).reshape(S, H * hd)
    return x + _mm("sh,hd->sd", o, p["wo"], control)


def _layer(cfg, control, x, p):
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    p = jax.tree_util.tree_map(f32, p)
    x = attention(cfg, control, x, p)
    h2 = _rms(x, p["mlp_norm"], cfg["norm_eps"])
    a = jax.nn.silu(_mm("sd,df->sf", h2, p["w_gate"], control))
    u = _mm("sd,df->sf", h2, p["w_up"], control)
    return x + _mm("sf,fd->sd", a * u, p["w_down"], control), None


@functools.partial(jax.jit, static_argnames=("cfg_items", "n_out", "control"))
def _logits(params, tokens, start, *, cfg_items, n_out, control):
    cfg = dict(cfg_items)
    x = params["embed"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(functools.partial(_layer, cfg, control), x, params["blocks"])
    h = jax.lax.dynamic_slice_in_dim(x, start, n_out, axis=0)
    h = _rms(h, params["final_norm"].astype(jnp.float32), cfg["norm_eps"])
    head = params["embed"].T if cfg["tie_embeddings"] else params["lm_head"]
    return _mm("sd,dv->sv", h, head.astype(jnp.float32), control)


def served_logits(cfg: dict, params, prompt, out, *, control=False, bucket=256):
    """Logits (len(out), V) that predict each served token `out[j]` from
    `prompt + out[:j]`. The sequence is padded at its end to a multiple of
    `bucket` (causal attention: padding after a position never reaches it),
    so one compiled program serves many lengths."""
    seq = list(prompt) + list(out[:-1])
    S = -(-len(seq) // bucket) * bucket
    n_out = -(-len(out) // bucket) * bucket
    tokens = jnp.asarray(seq + [0] * (S - len(seq)), jnp.int32)
    start = len(prompt) - 1
    S_pad = max(S, start + n_out)
    if S_pad > S:
        tokens = jnp.pad(tokens, (0, S_pad - S))
    keys = ("n_heads", "n_kv_heads", "head_dim", "norm_eps", "rope_theta",
            "tie_embeddings")
    items = tuple((k, cfg[k]) for k in keys)
    logits = _logits(params, tokens, jnp.int32(start), cfg_items=items,
                     n_out=n_out, control=control)
    return logits[: len(out)]
