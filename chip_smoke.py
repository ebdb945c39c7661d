#!/usr/bin/env python3
"""Smoke test of the tiered-KV serving path on one TPU chip.

  python3 chip_smoke.py [--seed N]

Drives ``repro.launch.serve`` in this process, once, with qwen3-1.7b at its
published widths (28 layers, d_model 2048, 16/8 heads, head_dim 128, vocab
151936) and random bf16 weights drawn from ``--seed``: 16 requests with
1024-token prompts and 32 new tokens each, page size 16, decode batch 8,
an HBM pool holding half the pages the requests need (so requests park,
pages are promoted and evicted), and the engine's 64-slot write log (so
the log compacts several times). The same requests then go through the dense
baseline, fed the tiered path's tokens.

It fails, with a message on stderr and a non-zero exit, unless: JAX runs
on a TPU; every request completes; parks, promotions, evictions and
compactions all happened; the compiled decode step and the compiled
compaction each contain a Pallas kernel (``tpu_custom_call``); and at every
step the dense path rates the tiered path's token within TOL of its own
best (relative logit gap, see ``serve.baseline_serve``). Details go to
stdout; the last line is one JSON object with the device JAX reports.
The persistent compilation cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, else to ``.jax_cache/`` in the checkout.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path
from typing import NoReturn

SRC = Path(__file__).resolve().parent / "src"

# Largest relative logit gap (max - chosen) / max(1, |max|) the dense path
# may see at any step for the tiered path's token. The two paths differ in
# attention arithmetic only: the dense path rounds scores and softmax
# weights to bf16, the tiered path keeps them in fp32 through the Pallas
# kernel and the log merge. That moves bf16 logits by a few ulps (2^-8 to
# 2^-7 of their magnitude each), and a near-tie can then flip the argmax.
# 1/16 admits about 8-16 ulps of drift; a token chosen from wrong KV lands
# on the order of the logits' spread (~1) below the top.
TOL = 1.0 / 16

REQUESTS, PROMPT, NEW = 16, 1024, 32
PAGE, BATCH = 16, 8
PAGES_PER_REQ = -(-(PROMPT + NEW) // PAGE)  # 66
HBM_PAGES = REQUESTS * PAGES_PER_REQ // 2  # half of what the requests need


def fail(msg: str) -> NoReturn:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def serve_argv(seed: int):
    return [
        "--arch", "qwen3-1.7b", "--full", "--seed", str(seed),
        "--requests", str(REQUESTS), "--prompt-len", str(PROMPT),
        "--new-tokens", str(NEW), "--page-size", str(PAGE),
        "--batch", str(BATCH), "--hbm-pages", str(HBM_PAGES),
        # one request's pages per step: the pool fills in tens of steps
        "--promote-pages", str(PAGES_PER_REQ),
    ]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if not (SRC / "repro").is_dir():
        fail(f"{SRC / 'repro'} not found: run chip_smoke.py from a checkout "
             "of the repository")
    sys.path.insert(0, str(SRC))

    import jax

    from repro.launch import serve
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    try:
        devices = jax.devices()
    except RuntimeError as e:  # no backend JAX can start
        fail(f"no TPU: JAX found no device ({e})")
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX runs on {dev.platform!r} ({dev.device_kind}); "
             "this smoke test needs a TPU chip")
    print(f"[smoke] device {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {cache_dir}")

    res = serve.run(serve.build_parser().parse_args(serve_argv(args.seed)))
    eng = res["engine"]
    st = eng.stats
    done = sum(r.done for r in eng.requests.values())
    if done != REQUESTS:
        fail(f"{done}/{REQUESTS} requests completed")
    for name in ("parks", "promoted_pages", "evicted_pages", "compactions"):
        if getattr(st, name) <= 0:
            fail(f"{name} = {getattr(st, name)}; the run did not exercise it")
    for name, prog in (("decode step", eng.step_fn),
                       ("compaction", eng.compact_fn)):
        if "tpu_custom_call" not in prog.as_text():
            fail(f"the compiled {name} contains no Pallas kernel")
    print("[smoke] Pallas kernels (tpu_custom_call) in the decode step and "
          "the compaction")

    t0 = time.perf_counter()
    _, gaps = serve.baseline_serve(res["spec"], res["params"], res["prompts"],
                                   NEW, follow=res["outs"])
    flat = [g for rid in sorted(gaps) for g in gaps[rid]]
    same = sum(g == 0.0 for g in flat)
    worst = max(flat)
    print(f"[smoke] dense baseline, fed the tiered tokens: {same}/{len(flat)} "
          f"steps pick the same token; largest relative logit gap "
          f"{worst:.6g} (tolerance {TOL:.6g}); host wall "
          f"{time.perf_counter() - t0:.1f}s (includes compilation)")
    if len(flat) != REQUESTS * NEW:
        fail(f"the dense baseline rated {len(flat)} tokens, not {REQUESTS * NEW}")
    if worst > TOL:
        fail(f"tiered and dense disagree: relative logit gap {worst:.6g} > {TOL}")

    peak = dev.memory_stats()["peak_bytes_in_use"]
    print(f"[smoke] peak device bytes in use: {peak} ({peak / 2**30:.2f} GiB)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
