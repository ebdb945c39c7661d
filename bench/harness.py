"""One run of one benchmark cell: set-up, measured window, per-layer
readings and the correctness check. `bench/run.py` is its command line.

Everything that belongs to a cell is found by name from `BENCHMARK.json`:
the configuration file it names, `bench/traffic/<traffic>.json`,
`bench/metrics/<metric>.py` for each per-layer metric and
`bench/limits/<cell>.json` for the correctness limit. The program is used
only through `ModelConfig`, `TieredEngine`, `Request`, `add_request`,
`step`, `requests` and `stats`.

A configuration of any family is added as files alone:

- `bench/configs/<name>.json`: the program's `ModelConfig` fields (a dict
  under a field that is a dataclass, such as `moe`, fills that dataclass),
  any keys of the benchmark's own, and `reference`, the name of its module;
- `bench/reference/<reference>.py`, the plain reference, which imports
  nothing of the program. It defines `served_logits(config, params, prompt,
  out, *, control=False)`, as `dense_gqa.py` does, and where the
  configuration is not a dense GQA decoder also `shapes(config)`, the
  weights' leaf table (`bench/weights.py`), and `decode_token_flops` and
  `paged_attn_work`, its work counts (`bench/flops.py`);
- its cells' traffic mixes, limit files and any per-layer metric readers.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import sys
import tempfile
import time
import typing
from pathlib import Path

import numpy as np

from bench import check, trace_reduce, traffic_gen, weights


@dataclasses.dataclass
class Cell:
    name: str
    root: Path
    config: dict
    mix: dict
    chips: int
    end_to_end: list
    per_layer: list
    limits: dict


def _read(path: Path) -> dict:
    return json.loads(path.read_text())


def load_cell(root: Path, name: str) -> Cell:
    bench = _read(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    limits = root / "bench" / "limits" / f"{name}.json"
    mine = lambda ms: [m for m in ms if name in m.get("workloads", [name])]  # noqa: E731
    return Cell(
        name=name, root=root,
        config=_read(root / configs[w["config"]]["file"]),
        mix=_read(root / "bench" / "traffic" / f"{w['traffic']}.json"),
        chips=int(w["chips"]),
        end_to_end=mine(bench["end_to_end"]), per_layer=mine(bench["per_layer"]),
        limits=_read(limits) if limits.exists() else {},
    )


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    contexts: list  # positions each token decoded in this step attended


@dataclasses.dataclass
class RunRecord:
    """What a per-layer metric reader gets."""
    cell: Cell
    steps: list
    window: tuple  # (t0, t1), host clock, seconds
    stats: dict  # ServeStats deltas over the window
    trace: dict  # trace_reduce.reduce_profile output
    peaks: dict  # bench/peaks.json row of this device
    itl: list = dataclasses.field(default_factory=list)  # itl_samples, seconds

    @property
    def tokens(self) -> int:
        return sum(len(s.contexts) for s in self.steps)


def load_metric(root: Path, name: str):
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileCounter:
    """Counts backend compilations while `armed` (none belong in the window)."""

    def __init__(self):
        import jax

        self.armed, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kw):
        if self.armed and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def model_config(config: dict):
    """The program's `ModelConfig` from a configuration file's dict. Keys
    that are not its fields stay for the benchmark's own use."""
    from repro.configs.base import ModelConfig

    return _dataclass(ModelConfig, config)


def _dataclass(cls, config: dict):
    """`cls` from the dict's keys that are its fields. A dict under a field
    whose type is a dataclass becomes that dataclass the same way; lists
    become tuples, as a frozen config holds its sequences."""
    types = typing.get_type_hints(cls)
    kw = {}
    for f in dataclasses.fields(cls):
        if f.name not in config:
            continue
        v = config[f.name]
        inner = [t for t in (types[f.name], *typing.get_args(types[f.name]))
                 if dataclasses.is_dataclass(t)]
        kw[f.name] = (_dataclass(inner[0], v) if isinstance(v, dict) and inner
                      else _tuples(v))
    return cls(**kw)


def _tuples(v):
    return tuple(_tuples(x) for x in v) if isinstance(v, list) else v


def _stats(eng) -> dict:
    return dataclasses.asdict(eng.stats)


def itl_samples(token_times: dict, done: dict, t0: float, t1: float) -> list:
    """Inter-token gaps whose later token falls in (t0, t1], plus, for each
    request unfinished at t1, its open gap from its last token to t1. A gap
    that opened before the window counts from the window's start, so that
    set-up (compilation on a first run) is not read as a gap."""
    gaps = []
    for rid, times in token_times.items():
        gaps += [b - max(a, t0) for a, b in zip(times, times[1:]) if t0 < b <= t1]
        if not done[rid]:
            gaps.append(t1 - max(times[-1], t0))
    return gaps


def measure(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
            log=sys.stderr, keep_trace: str | None = None):
    """Set-up and the measured window. Returns (result without the check,
    {rid: (prompt, served tokens)}). The caller has checked
    the device; `t_start` is the process's start on the host clock. With
    `keep_trace`, the profile is written under that directory and kept."""
    import jax
    from jax.profiler import TraceAnnotation

    from repro.core.tiering import TieredKVConfig
    from repro.models.api import ModelSpec
    from repro.serving.engine import Request, TieredEngine

    say = lambda *a: print("[bench]", *a, file=log, flush=True)  # noqa: E731
    dev = jax.devices()[0]
    compiles = CompileCounter()
    cfg = model_config(cell.config)
    reqs = traffic_gen.requests(cell.mix, seed, cfg.vocab)
    params = jax.block_until_ready(weights.make(cell.config, seed, root=cell.root))
    say(f"weights at {time.perf_counter() - t_start:.3f} s")
    kv = TieredKVConfig(**cell.mix["engine"], max_requests=len(reqs))
    eng = TieredEngine(ModelSpec(cfg), params, kv)
    say(f"engine at {time.perf_counter() - t_start:.3f} s, compiled {eng.compile_seconds}")
    token_times = {}
    for rid, prompt, new in reqs:
        with TraceAnnotation("bench.add_request"):
            eng.add_request(Request(rid=rid, prompt=prompt, max_new_tokens=new))
        token_times[rid] = [time.perf_counter()]
    say(f"admitted at {time.perf_counter() - t_start:.3f} s")
    requests = eng.requests

    def one_step():
        before = {rid: len(r.out) for rid, r in requests.items() if not r.done}
        t0 = time.perf_counter()
        with TraceAnnotation("bench.step"):
            eng.step()
        t1 = time.perf_counter()
        contexts = []
        with TraceAnnotation("bench.bookkeeping"):
            for rid, n in before.items():
                r = requests[rid]
                for j in range(n, len(r.out)):
                    contexts.append(len(r.prompt) + j)
                    token_times[rid].append(t1)
        return Step(t0, t1, contexts)

    warm = cell.mix["warmup"]
    n_warm = 0
    while (n_warm < warm["min_steps"] or (warm.get("until_all_scheduled") and any(
            len(r.out) < 2 and not r.done for r in requests.values()))):
        if n_warm >= 10_000:
            raise RuntimeError("warm-up did not schedule every request")
        st = one_step()
        n_warm += 1
        say(f"warm-up step {n_warm} {st.t1 - st.t0:.4f} s {len(st.contexts)}")
    setup_s = time.perf_counter() - t_start
    say(f"set-up {setup_s:.3f} s, {n_warm} warm-up steps, stats {_stats(eng)}")

    stats0 = _stats(eng)
    tmp = tempfile.TemporaryDirectory() if trace and not keep_trace else None
    trace_dir = keep_trace or (tmp.name if tmp else None)
    if trace:
        # no Python tracer: it records every Python call (tens of MB a second)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level, opts.host_tracer_level = 0, 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    steps = []
    compiles.armed = True
    with TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds and not all(
                r.done for r in requests.values()):
            steps.append(one_step())
            st = steps[-1]
            say(f"step {len(steps)} {st.t0 - t0:.4f} {st.t1 - t0:.4f} {len(st.contexts)}")
        t1 = steps[-1].t1 if steps else time.perf_counter()
    compiles.armed = False
    if trace:
        jax.profiler.stop_trace()
    stats = {k: v - stats0[k] for k, v in _stats(eng).items()}
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")  # None off a chip
    tokens = sum(len(s.contexts) for s in steps)
    window_s = t1 - t0
    say(f"window {window_s:.3f} s, {len(steps)} steps, {tokens} tokens, "
        f"{compiles.count} compiles in the window, stats {stats}")
    if compiles.count:
        say(f"WARNING: {compiles.count} backend compiles inside the window")

    itl = itl_samples(token_times, {rid: r.done for rid, r in requests.items()}, t0, t1)
    e2e = {
        "decode_tok_s": tokens / window_s,
        "itl_p90_ms": float(np.percentile(itl, 90)) * 1e3 if itl else None,
        "peak_hbm_gib": peak / 2**30 if peak is not None else None,
        "setup_s": setup_s,
    }
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": False, "attempted": len(reqs), "failed": len(reqs)}
    breakdown = None
    if trace:
        red = trace_reduce.reduce_file(max(Path(trace_dir).rglob("*.xplane.pb"),
                                           key=lambda f: f.stat().st_mtime))
        if tmp:
            tmp.cleanup()
        peaks = _read(cell.root / "bench" / "peaks.json").get(dev.device_kind)
        if peaks is None:
            raise KeyError(f"no peaks for device kind {dev.device_kind!r} in bench/peaks.json")
        rec = RunRecord(cell, steps, (t0, t1), stats, red, peaks, itl)
        metrics = {}
        for m in cell.per_layer:
            value = load_metric(cell.root, m["name"])(rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        lo, hi = red["window_ns"]
        device.update(busy_s=red["busy_ns"] / 1e9, window_s=(hi - lo) / 1e9)
        breakdown = {"device_ops": trace_reduce.top_ops(red),
                     "idle_gaps": trace_reduce.idle_gaps(red)}
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if e2e.get(m["name"]) is not None}

    served = {rid: (list(r.prompt), list(r.out)) for rid, r in requests.items()}
    result.update(metrics=metrics, device=device)
    if breakdown is not None:
        result["breakdown"] = breakdown
    return result, served


def judge(cell: Cell, seed: int, served: dict, result: dict,
          log=sys.stderr) -> dict:
    """Compare the served tokens with the reference; fill in `correct`,
    `failed` and, last, `check`. Runs after the program's state is freed."""
    t = time.perf_counter()
    gaps = check.gaps(cell.root, cell.config, seed, served)
    limit = cell.limits.get("max_logit_gap", {}).get("limit")
    widest = max(gaps.values())
    failed = sum(g > limit for g in gaps.values()) if limit is not None else len(gaps)
    n_tok = sum(len(o) for _, o in served.values())
    print(f"[bench] check: {len(gaps)} requests, {n_tok} served tokens, "
          f"{time.perf_counter() - t:.1f} s", file=log)
    result.update(correct=limit is not None and failed == 0, failed=failed)
    result["check"] = {"max_logit_gap": {"value": widest, "limit": limit}}
    print(f"max_logit_gap {widest!r} limit {limit!r}", file=log, flush=True)
    return result


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        log=sys.stderr, keep_trace: str | None = None) -> dict:
    """One run of the cell; returns the result line's dict."""
    result, served = measure(cell, seed, seconds, trace, t_start, log, keep_trace)
    gc.collect()  # the engine, its pools and the weights are gone by now
    return judge(cell, seed, served, result, log)
