"""Integration tests for the SkyByte tiering runtime + serving engine.

The decisive test: the tiered engine's greedy decode must be TOKEN-IDENTICAL
to plain dense decode, under page-pool pressure (parking = coordinated
context switches, promotion/eviction = adaptive migration) and across log
compactions — i.e. the paper's mechanisms change performance, never
results.
"""
import collections
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


@pytest.mark.parametrize("case", list(CASES))
def test_compaction_flushes_the_pages_logged_since_the_last(model, case):
    """The host alone holds the engine's metadata: the device state is the
    six data arrays. Each compaction flushes, from the host's log index,
    the (request, page) of every token decoded since the last compaction,
    in ascending order, with their counts: every page to the host tier, the
    HBM-resident ones to their HBM slot too."""
    spec, params = model
    kv = CASES[case]
    eng = TieredEngine(spec, params, kv)
    for rid, p in {0: list(range(7, 27)), 1: list(range(40, 75)),
                   2: list(range(5, 18))}.items():
        eng.add_request(Request(rid=rid, prompt=p, max_new_tokens=20))
    assert set(eng.state) == {"hbm_k", "hbm_v", "host_k", "host_v", "log_k", "log_v"}
    flushes, real = [], eng.compact_fn

    def spy(*args):
        flushes.append((np.asarray(args[-2]), np.asarray(args[-1]), eng.page_table.copy()))
        return real(*args)

    eng.compact_fn = spy
    logged = collections.Counter()  # (rid, page) -> tokens decoded into it
    while not all(r.done for r in eng.requests.values()):
        served = {rid: len(r.out) for rid, r in eng.requests.items() if not r.done}
        flushed = (eng.stats.flushed_pages, eng.stats.flushed_tokens)
        n_flushes = len(flushes)
        eng.step()
        if len(flushes) > n_flushes:  # compacted before this step's decode
            hbm, host, table = flushes[-1]
            pages = sorted(logged)
            assert host[host[:, 0] >= 0].tolist() == [
                [rid, lg, tiering.host_slot(kv, rid, lg)] for rid, lg in pages]
            assert hbm[hbm[:, 0] >= 0].tolist() == [
                [rid, lg, int(table[rid, lg])] for rid, lg in pages if table[rid, lg] >= 0]
            assert (eng.stats.flushed_pages - flushed[0],
                    eng.stats.flushed_tokens - flushed[1]) == (len(pages), sum(logged.values()))
            logged.clear()
        for rid, n in served.items():
            r = eng.requests[rid]
            if len(r.out) > n:  # decoded at position prompt + n - 1
                logged[(rid, (len(r.prompt) + n - 1) // kv.page_size)] += 1
    assert len(flushes) == eng.stats.compactions
    if case == "compaction":
        assert len(flushes) > 1


def _pools(rng, n_hbm=12, n_host=20):
    """HBM K and V pools, then the host tier's: (L, pages, page_size, KV, hd)."""
    return [jnp.asarray(rng.standard_normal((2, n, 4, 2, 8)), jnp.bfloat16)
            for n in (n_hbm, n_hbm, n_host, n_host)]


def _promote_fn(pools, budget):
    hk, hv, sk, sv = pools
    return jax.jit(tiering.promote_pages, donate_argnums=(0, 1)).lower(
        hk, hv, sk, sv, jax.ShapeDtypeStruct((budget, 2), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32)).compile()


@pytest.mark.parametrize("n", [0, 1, 5, 8])
def test_promote_pages_equals_a_per_page_copy(n):
    """The promotion program against a page-by-page numpy copy: random
    distinct HBM slots, the first real row aimed at slot 0, padding rows
    of -1 after the ``n`` real ones (n = 8 fills the budget); padding
    writes nothing."""
    budget = 8
    rng = np.random.default_rng(n)
    pools = _pools(rng)
    want = [np.asarray(p).copy() for p in pools[:2]]
    src = rng.choice(20, size=n, replace=False)
    dst = np.concatenate([[0], rng.choice(np.arange(1, 12), size=budget, replace=False)])[:n]
    for s, d in zip(src, dst):
        for w, host in zip(want, pools[2:]):
            w[:, d] = np.asarray(host)[:, s]
    pairs = np.full((budget, 2), -1, np.int32)
    pairs[:n] = np.stack([src, dst], axis=1)
    got = _promote_fn(pools, budget)(*pools, jnp.asarray(pairs), np.int32(n))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), w)


def test_promote_pages_donates_the_hbm_pools(model):
    """The engine's promotion program takes the HBM pools in place: the
    arrays it was given are deleted, the slow pools are only read."""
    spec, params = model
    eng = TieredEngine(spec, params, CASES["pool_pressure"])
    st = eng.state
    old = {k: st[k] for k in ("hbm_k", "hbm_v", "host_k", "host_v")}
    pairs = np.full((eng.kv.promote_pages_per_step, 2), -1, np.int32)
    pairs[0] = (3, 0)
    new_k, new_v = eng.promote_fn(old["hbm_k"], old["hbm_v"], old["host_k"],
                                  old["host_v"], jnp.asarray(pairs), np.int32(1))
    assert old["hbm_k"].is_deleted() and old["hbm_v"].is_deleted()
    assert not (old["host_k"].is_deleted() or old["host_v"].is_deleted())
    np.testing.assert_array_equal(np.asarray(new_k[:, 0]), np.asarray(old["host_k"][:, 3]))
    np.testing.assert_array_equal(np.asarray(new_v[:, 0]), np.asarray(old["host_v"][:, 3]))


@pytest.mark.parametrize("case", list(CASES))
def test_one_promotion_launch_per_step_that_promotes(model, case):
    """Each step launches the promotion program at most once, for exactly
    the pages its host table newly maps, to distinct HBM slots; the parks,
    promoted and evicted pages the stats count equal those worked out step
    by step from the host table."""
    spec, params = model
    kv = CASES[case]
    eng = TieredEngine(spec, params, kv)
    for rid, p in {0: list(range(7, 27)), 1: list(range(40, 75)),
                   2: list(range(5, 18))}.items():
        eng.add_request(Request(rid=rid, prompt=p, max_new_tokens=20))
    launches, real = [], eng.promote_fn

    def spy(hk, hv, sk, sv, pairs, n):
        launches.append((np.asarray(pairs), int(n)))
        return real(hk, hv, sk, sv, pairs, n)

    eng.promote_fn = spy
    parks = promoted = evicted = promoting_steps = 0
    while not all(r.done for r in eng.requests.values()):
        # a compaction due now moves the watermarks before the residency check
        compacts = eng.log_tail + kv.batch > kv.log_slots
        marks = eng.lengths if compacts else eng.compacted
        before = eng.page_table.copy()
        parks += sum(
            bool((before[r.rid, :-(-int(marks[r.rid]) // kv.page_size)] < 0).any())
            for r in eng.requests.values() if not r.done)
        n_launches = len(launches)
        eng.step()
        after = eng.page_table
        new = np.argwhere((before < 0) & (after >= 0))
        promoted += len(new)
        evicted += int(((before >= 0) & (after < 0)).sum())
        promoting_steps += bool(len(new))
        assert len(launches) - n_launches == bool(len(new))
        if len(new):
            pairs, n = launches[-1]
            assert n == len(new) and (pairs[n:] == -1).all()
            assert len(set(pairs[:n, 1])) == n  # distinct HBM slots
            assert sorted(map(tuple, pairs[:n].tolist())) == sorted(
                (tiering.host_slot(kv, rid, lg), int(after[rid, lg])) for rid, lg in new)
    st = eng.stats
    assert st.promote_calls == len(launches) == promoting_steps > 0
    assert (st.parks, st.promoted_pages, st.evicted_pages) == (parks, promoted, evicted)
    if case == "serial_batch1":  # the case whose pool is too small to keep pages
        assert evicted > 0


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
    assert set(eng.compile_seconds) == {"decode", "compact", "promote"}
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
