"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.kv_log_append.ops import kv_log_append
from repro.kernels.kv_log_append.ref import kv_log_append_ref
from repro.kernels.log_compact.ops import log_compact
from repro.kernels.log_compact.ref import log_compact_ref
from repro.kernels.paged_attention.ops import paged_decode_attention
from repro.kernels.paged_attention.ref import paged_decode_attention_ref


def _rand(rng, shape, dtype):
    x = rng.normal(size=shape).astype(np.float32)
    return jnp.asarray(x, dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,H,KV,hd,page,P,N",
    [
        (2, 4, 2, 32, 8, 8, 3),
        (3, 8, 4, 64, 16, 16, 4),
        (1, 6, 2, 16, 4, 6, 5),  # GQA group 3
        (4, 4, 4, 128, 8, 12, 2),  # MHA
    ],
)
def test_paged_attention_sweep(B, H, KV, hd, page, P, N, dtype):
    rng = np.random.default_rng(B * 100 + H)
    q = _rand(rng, (B, H, hd), dtype)
    kp = _rand(rng, (P, page, KV, hd), dtype)
    vp = _rand(rng, (P, page, KV, hd), dtype)
    pt = jnp.asarray(
        rng.choice(P, size=B * N, replace=B * N > P).reshape(B, N), jnp.int32
    )
    pt = pt.at[0, N - 1].set(-1)  # one non-resident page
    lengths = jnp.asarray(rng.integers(1, N * page + 1, size=B), jnp.int32)
    ref = paged_decode_attention_ref(q, kp, vp, pt, lengths)
    out = paged_decode_attention(q, kp, vp, pt, lengths, mode="interpret")
    tol = 2e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(
        np.asarray(ref, np.float32), np.asarray(out, np.float32), atol=tol, rtol=tol
    )


def test_paged_attention_with_log_merge():
    rng = np.random.default_rng(7)
    B, H, KV, hd, page, P, N, S = 3, 8, 4, 64, 16, 16, 4, 8
    q = _rand(rng, (B, H, hd), jnp.float32)
    kp = _rand(rng, (P, page, KV, hd), jnp.float32)
    vp = _rand(rng, (P, page, KV, hd), jnp.float32)
    pt = jnp.asarray(rng.choice(P, size=B * N, replace=False).reshape(B, N), jnp.int32)
    log_k = _rand(rng, (S, KV, hd), jnp.float32)
    log_v = _rand(rng, (S, KV, hd), jnp.float32)
    meta = -jnp.ones((S, 2), jnp.int32)
    meta = meta.at[0].set(jnp.array([1, 60])).at[1].set(jnp.array([1, 61]))
    # pages valid < 48 (compaction watermark), log covers the rest
    page_lengths = jnp.asarray([48, 48, 48], jnp.int32)
    lengths = jnp.asarray([48, 62, 48], jnp.int32)
    ref = paged_decode_attention_ref(
        q, kp, vp, pt, lengths, log_k, log_v, meta, page_lengths=page_lengths
    )
    out = paged_decode_attention(
        q, kp, vp, pt, lengths, log_k, log_v, meta, page_lengths=page_lengths,
        mode="interpret",
    )
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("B,S,H,KV,hd,bq,bk", [
    (2, 64, 4, 2, 32, 16, 16),
    (1, 128, 8, 8, 64, 32, 64),
    (2, 96, 6, 2, 16, 32, 32),
])
def test_flash_attention_sweep(B, S, H, KV, hd, bq, bk, causal, dtype):
    rng = np.random.default_rng(S + H)
    q = _rand(rng, (B, S, H, hd), dtype)
    k = _rand(rng, (B, S, KV, hd), dtype)
    v = _rand(rng, (B, S, KV, hd), dtype)
    ref = flash_attention_ref(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk)
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(
        np.asarray(ref, np.float32), np.asarray(out, np.float32), atol=tol, rtol=tol
    )


@pytest.mark.parametrize("L,S,B,KV,hd,tail", [
    (2, 32, 4, 2, 16, 0), (3, 64, 8, 4, 32, 17), (1, 16, 2, 1, 8, 14),
])
def test_kv_log_append_sweep(L, S, B, KV, hd, tail):
    rng = np.random.default_rng(L * S)
    log_k = _rand(rng, (L, S, KV, hd), jnp.float32)
    log_v = _rand(rng, (L, S, KV, hd), jnp.float32)
    meta = -jnp.ones((S, 2), jnp.int32)
    kn = _rand(rng, (L, B, KV, hd), jnp.float32)
    vn = _rand(rng, (L, B, KV, hd), jnp.float32)
    req = jnp.asarray(rng.integers(0, 8, B), jnp.int32)
    pos = jnp.asarray(rng.integers(0, 100, B), jnp.int32)
    r = kv_log_append_ref(log_k, log_v, meta, jnp.int32(tail), kn, vn, req, pos)
    o = kv_log_append(log_k, log_v, meta, jnp.int32(tail), kn, vn, req, pos)
    for a, b in zip(r, o):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=0)


@pytest.mark.parametrize("L,P,page,KV,hd,S,F", [
    (2, 6, 8, 2, 16, 32, 4), (1, 4, 16, 4, 32, 16, 2),
])
def test_log_compact_sweep(L, P, page, KV, hd, S, F):
    rng = np.random.default_rng(P * page)
    kp = _rand(rng, (L, P, page, KV, hd), jnp.float32)
    vp = _rand(rng, (L, P, page, KV, hd), jnp.float32)
    log_k = _rand(rng, (L, S, KV, hd), jnp.float32)
    log_v = _rand(rng, (L, S, KV, hd), jnp.float32)
    meta = -jnp.ones((S, 2), jnp.int32)
    # scatter a handful of log entries over (request, position)
    for i in range(S // 2):
        meta = meta.at[i].set(
            jnp.array([int(rng.integers(0, 3)), int(rng.integers(0, P * page))])
        )
    # engine invariant: flush targets reference distinct (request, logical)
    # pairs and distinct pool slots
    slots = rng.choice(P, size=F - 1, replace=False)
    pairs = rng.choice(3 * 3, size=F - 1, replace=False)
    ft_rows = [[int(pr // 3), int(pr % 3), int(s)] for pr, s in zip(pairs, slots)]
    ft_rows.append([-1, 0, 0])  # padding row
    ft = jnp.asarray(ft_rows, jnp.int32)
    rk, rv = log_compact_ref(kp, vp, log_k, log_v, meta, ft)
    ok, ov = log_compact(kp, vp, log_k, log_v, meta, ft, mode="interpret")
    np.testing.assert_allclose(np.asarray(rk), np.asarray(ok), atol=1e-6)
    np.testing.assert_allclose(np.asarray(rv), np.asarray(ov), atol=1e-6)
