"""Serving launcher — the SkyByte tiered-KV engine end to end.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --requests 6 \
      --tiering skybyte
  PYTHONPATH=src python -m repro.launch.serve --tiering baseline   # dense KV
  PYTHONPATH=src python -m repro.launch.serve --full ...  # published widths

Reports the paper's metrics for the serving analogue: parks (coordinated
context switches), promoted/evicted pages (adaptive migration), compactions
and the coalescing ratio (write-log), and the engine's device->host reads
(``ServeStats.host_reads``): the tokens of each prefill and decode step.
Compilation of the decode step and the compaction is reported as set-up
time. The wall times printed are host clock readings that include prefill
compilation; they are not measurements.
Weights are random (bf16, drawn from --seed); prompts are random tokens.
"""
from __future__ import annotations

import argparse
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config, get_reduced
from repro.core.tiering import TieredKVConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models.api import ModelSpec
from repro.serving.engine import Request, TieredEngine


def baseline_serve(spec, params, prompts, n_new, follow=None):
    """Dense (non-tiered) reference serving loop: full KV per request.

    Returns (outs, gaps). ``outs[rid]`` are the dense path's greedy picks.
    With ``follow`` (rid -> tokens), each step feeds the followed token
    instead of its own pick, and ``gaps[rid][i]`` is the followed token's
    relative logit gap at step i, (max(logits) - logits[token]) /
    max(1, |max(logits)|): 0 where the dense path picks the same token.
    Without ``follow``, ``gaps`` is empty.
    """
    prefill = jax.jit(spec.prefill)
    step = jax.jit(spec.decode_step)
    outs: Dict[int, List[int]] = {}
    gaps: Dict[int, List[float]] = {}
    for rid, p in prompts.items():
        toks = jnp.asarray(p, jnp.int32)[None]
        logits, cache = prefill(params, toks)
        S = len(p)
        maxlen = S + n_new + 4
        dc = spec.init_cache(1, maxlen)
        for kk in ("k", "v"):
            dc[kk] = jnp.pad(cache[kk], [(0, 0), (0, 0), (0, maxlen - S), (0, 0), (0, 0)])
        pos = jnp.int32(S)
        out, gap = [], []
        for i in range(n_new):
            if i:
                logits, dc = step(params, dc, jnp.asarray([[fed]], jnp.int32), pos)
                pos = pos + 1
            out.append(int(jnp.argmax(logits[0])))
            fed = out[-1]
            if follow is not None:
                fed = follow[rid][i]
                row = logits[0].astype(jnp.float32)
                top = jnp.max(row)
                gap.append(float((top - row[fed]) / jnp.maximum(1.0, jnp.abs(top))))
        outs[rid] = out
        if follow is not None:
            gaps[rid] = gap
    return outs, gaps


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", choices=list(ARCH_IDS), default="qwen3-1.7b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false",
                    help="the published widths instead of the reduced preset")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--new-tokens", type=int, default=24)
    ap.add_argument("--tiering", choices=["skybyte", "baseline"], default="skybyte")
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--hbm-pages", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--promote-pages", type=int, default=4,
                    help="host->HBM page copies per engine step")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def kv_config(args: argparse.Namespace) -> TieredKVConfig:
    return TieredKVConfig(
        page_size=args.page_size,
        n_hbm_pages=args.hbm_pages,
        max_requests=max(args.requests, 2),
        max_pages_per_req=(args.prompt_len + args.new_tokens) // args.page_size + 2,
        log_slots=64,
        batch=min(args.batch, args.requests),
        promote_pages_per_step=args.promote_pages,
    )


def run(args: argparse.Namespace) -> Dict[str, Any]:
    """Serve ``args.requests`` random prompts on the path ``args.tiering``
    names. Returns the model, the prompts, each request's tokens (``outs``)
    and, for the tiered path, the engine."""
    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    assert cfg.family in ("dense", "moe", "vlm"), (
        "tiered serving targets GQA decoder families; "
        f"{cfg.family} decode runs via repro.launch.steps.build_serve_step"
    )
    spec = ModelSpec(cfg)
    params = spec.init(jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng(args.seed)
    prompts = {
        rid: [int(t) for t in rng.integers(1, cfg.vocab - 1, size=args.prompt_len)]
        for rid in range(args.requests)
    }
    print(f"[serve] arch={cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} head_dim={cfg.resolved_head_dim} "
          f"vocab={cfg.vocab} params={spec.param_count():,} "
          f"requests={args.requests} prompt={args.prompt_len} new={args.new_tokens}")
    res: Dict[str, Any] = {"spec": spec, "params": params, "prompts": prompts,
                           "engine": None}

    if args.tiering == "baseline":
        t0 = time.perf_counter()
        outs, _ = baseline_serve(spec, params, prompts, args.new_tokens)
        total = sum(len(o) for o in outs.values())
        print(f"[serve/baseline] {total} tokens; host wall "
              f"{time.perf_counter() - t0:.1f}s (includes compilation)")
        res["outs"] = outs
        return res

    kv = kv_config(args)
    eng = TieredEngine(spec, params, kv)
    print("[serve/skybyte] set-up: compile "
          + ", ".join(f"{k} {v:.2f}s" for k, v in eng.compile_seconds.items()))
    t0 = time.perf_counter()
    for rid, p in prompts.items():
        eng.add_request(Request(rid=rid, prompt=p, max_new_tokens=args.new_tokens))
    t1 = time.perf_counter()
    stats = eng.run(max_steps=5000)
    t2 = time.perf_counter()
    print(f"[serve/skybyte] {stats.decoded_tokens} tokens in {stats.steps} decode "
          f"steps; host wall: admission {t1 - t0:.1f}s (includes prefill "
          f"compilation), run {t2 - t1:.1f}s")
    print(f"  parks (ctx switches)      : {stats.parks}")
    print(f"  promoted / evicted pages  : {stats.promoted_pages} / {stats.evicted_pages}")
    print(f"  compactions               : {stats.compactions}")
    print(f"  coalesce ratio (tok/page) : {stats.coalesce_ratio:.2f}")
    print(f"  host reads (device->host) : {stats.host_reads} "
          f"({stats.host_reads / max(stats.steps, 1):.1f} per step, admission included)")
    done = sum(r.done for r in eng.requests.values())
    print(f"  completed requests        : {done}/{len(eng.requests)}")
    res["engine"] = eng
    res["outs"] = {rid: list(r.out) for rid, r in eng.requests.items()}
    return res


def main(argv: Optional[List[str]] = None) -> None:
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    run(args)


if __name__ == "__main__":
    main()
