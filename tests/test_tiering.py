"""Integration tests for the SkyByte tiering runtime + serving engine.

The decisive test: the tiered engine's greedy decode must be TOKEN-IDENTICAL
to plain dense decode, under page-pool pressure (parking = coordinated
context switches, promotion/eviction = adaptive migration) and across log
compactions — i.e. the paper's mechanisms change performance, never
results.
"""
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core import tiering
from repro.core.tiering import TieredKVConfig
from repro.models.api import ModelSpec
from repro.serving.engine import Request, TieredEngine


@pytest.fixture(scope="module")
def model():
    cfg = get_reduced("qwen3-1.7b")
    spec = ModelSpec(cfg)
    params = spec.init(jax.random.PRNGKey(0))
    return spec, params


def ref_decode(spec, params, prompt, n_new):
    toks = jnp.asarray(prompt, jnp.int32)[None]
    logits, cache = spec.prefill(params, toks)
    out = [int(jnp.argmax(logits[0]))]
    S = len(prompt)
    maxlen = S + n_new + 4
    dc = spec.init_cache(1, maxlen)
    for kk in ("k", "v"):
        dc[kk] = jnp.pad(cache[kk], [(0, 0), (0, 0), (0, maxlen - S), (0, 0), (0, 0)])
    pos = jnp.int32(S)
    for _ in range(n_new - 1):
        logits, dc = spec.decode_step(
            params, dc, jnp.asarray([[out[-1]]], jnp.int32), pos
        )
        out.append(int(jnp.argmax(logits[0])))
        pos = pos + 1
    return out


def run_engine(spec, params, prompts, kv, n_new):
    eng = TieredEngine(spec, params, kv)
    for rid, p in prompts.items():
        eng.add_request(Request(rid=rid, prompt=p, max_new_tokens=n_new))
    stats = eng.run(max_steps=2000)
    return eng, stats


CASES = {
    "no_pressure": TieredKVConfig(page_size=8, n_hbm_pages=32, max_requests=4,
                                  max_pages_per_req=12, log_slots=256, batch=2,
                                  promote_pages_per_step=8),
    "compaction": TieredKVConfig(page_size=8, n_hbm_pages=32, max_requests=4,
                                 max_pages_per_req=12, log_slots=8, batch=2,
                                 promote_pages_per_step=8),
    "pool_pressure": TieredKVConfig(page_size=8, n_hbm_pages=16, max_requests=4,
                                    max_pages_per_req=12, log_slots=32, batch=2,
                                    promote_pages_per_step=2),
    "serial_batch1": TieredKVConfig(page_size=8, n_hbm_pages=9, max_requests=4,
                                    max_pages_per_req=12, log_slots=32, batch=1,
                                    promote_pages_per_step=8),
}


@pytest.mark.parametrize("case", list(CASES))
def test_engine_equals_dense_decode(model, case):
    spec, params = model
    kv = CASES[case]
    prompts = {0: list(range(7, 27)), 1: list(range(40, 75)),
               2: list(range(5, 18))}
    n_new = 20
    refs = {rid: ref_decode(spec, params, p, n_new) for rid, p in prompts.items()}
    eng, stats = run_engine(spec, params, prompts, kv, n_new)
    for rid in prompts:
        assert eng.requests[rid].out == refs[rid], (
            f"{case}: req {rid} diverged (parks={stats.parks}, "
            f"compactions={stats.compactions})"
        )
    if case == "pool_pressure":
        assert stats.parks > 0, "pressure case should trigger context switches"
        assert stats.promoted_pages > 0
    if case == "compaction":
        assert stats.compactions > 0


def assert_host_metadata_mirrors_device(eng):
    st = {k: np.asarray(eng.state[k])
          for k in ("page_table", "compacted", "lengths", "log_tail", "log_meta")}
    np.testing.assert_array_equal(eng.page_table, st["page_table"])
    np.testing.assert_array_equal(eng.compacted, st["compacted"])
    np.testing.assert_array_equal(eng.lengths, st["lengths"])
    assert eng.log_tail == int(st["log_tail"])
    dirty = {}
    for owner, pos in st["log_meta"]:
        if owner >= 0 and pos >= 0:
            key = (int(owner), int(pos) // eng.kv.page_size)
            dirty[key] = dirty.get(key, 0) + 1
    assert eng.dirty == dirty


@pytest.mark.parametrize("case", list(CASES))
def test_host_metadata_mirrors_device(model, case):
    """The page table, watermarks, lengths, log tail and dirty pages the
    policy reads on the host equal the device's after every step, so also
    just before each compaction, which flushes the dirty pages."""
    spec, params = model
    eng = TieredEngine(spec, params, CASES[case])
    for rid, p in {0: list(range(7, 27)), 1: list(range(40, 75)),
                   2: list(range(5, 18))}.items():
        eng.add_request(Request(rid=rid, prompt=p, max_new_tokens=20))
    assert_host_metadata_mirrors_device(eng)
    while not all(r.done for r in eng.requests.values()):
        eng.step()
        assert_host_metadata_mirrors_device(eng)
    assert eng.stats.steps > 0


def test_engine_pallas_path(model, monkeypatch):
    """Same equivalence through the Pallas kernels (interpret mode)."""
    monkeypatch.setattr(tiering, "kernel_mode", lambda: "interpret")
    spec, params = model
    kv = TieredKVConfig(page_size=8, n_hbm_pages=16, max_requests=2,
                        max_pages_per_req=8, log_slots=32, batch=2,
                        promote_pages_per_step=4)
    prompts = {0: list(range(3, 19)), 1: list(range(21, 40))}
    n_new = 10
    refs = {rid: ref_decode(spec, params, p, n_new) for rid, p in prompts.items()}
    eng, stats = run_engine(spec, params, prompts, kv, n_new)
    for rid in prompts:
        assert eng.requests[rid].out == refs[rid]


def test_coalescing_reduces_page_writes(model):
    """The paper's core write-path claim, restated for serving: with the
    write log, page-granular writes ~ tokens/page_size, not ~ tokens."""
    spec, params = model
    kv = TieredKVConfig(page_size=8, n_hbm_pages=32, max_requests=2,
                        max_pages_per_req=12, log_slots=16, batch=1,
                        promote_pages_per_step=8)
    prompts = {0: list(range(10, 34))}
    eng, stats = run_engine(spec, params, prompts, kv, n_new=32)
    assert stats.compactions >= 1
    # without a log, every decoded token would dirty (and flush) its page:
    # flushed pages must be well below decoded tokens
    assert stats.flushed_pages < stats.decoded_tokens
    assert stats.coalesce_ratio > 1.5


def test_serve_run_agrees_with_dense_baseline():
    """The entry point chip_smoke.py drives, at reduced width: under pool
    pressure and compaction every request completes, every mechanism fires,
    and the dense baseline fed the tiered tokens picks each one itself."""
    from repro.launch import serve

    n_new = 40  # 4 x 39 decoded tokens fill the 64-slot log twice
    args = serve.build_parser().parse_args([
        "--requests", "4", "--prompt-len", "20", "--new-tokens", str(n_new),
        "--page-size", "8", "--hbm-pages", "10", "--batch", "2",
        "--promote-pages", "2",
    ])
    res = serve.run(args)
    eng = res["engine"]
    assert all(r.done for r in eng.requests.values())
    st = eng.stats
    assert min(st.parks, st.promoted_pages, st.evicted_pages, st.compactions) > 0
    assert set(eng.compile_seconds) == {"decode", "compact"}
    _, gaps = serve.baseline_serve(res["spec"], res["params"], res["prompts"],
                                   n_new, follow=res["outs"])
    assert sorted(gaps) == sorted(res["prompts"])
    assert all(len(g) == n_new and max(g) == 0.0 for g in gaps.values())


@pytest.mark.parametrize("env_dir", [None, "placed-from-outside"])
def test_compile_cache_location(monkeypatch, tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins untouched; otherwise one fixed
    directory in the checkout."""
    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        if env_dir is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            got = compile_cache.enable_compile_cache()
            assert got == str(compile_cache.REPO_CACHE_DIR)
            assert compile_cache.REPO_CACHE_DIR.parent == (
                Path(__file__).resolve().parent.parent
            )
            assert jax.config.jax_compilation_cache_dir == got
        else:
            want = str(tmp_path / env_dir)
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
            jax.config.update("jax_compilation_cache_dir", want)  # as read at import
            assert compile_cache.enable_compile_cache() == want
            assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
