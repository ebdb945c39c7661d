"""Continuous-batching serving engine with the SkyByte scheduler.

The engine is the OS half of the co-design: it owns policy (who runs,
what gets promoted/evicted, when the log compacts) while core/tiering.py
owns the device data path — mirroring the paper's host-OS / SSD-controller
split.

Per decode step:
  1. residency check — a request is READY iff all its KV pages are in the
     HBM pool. Non-resident requests are PARKED (the coordinated context
     switch: the predicted fetch delay, pages_missing * fetch_page_us,
     always exceeds the park threshold) and their pages are queued for
     promotion.
  2. promotion — up to ``promote_pages_per_step`` host->HBM page copies
     (the migration bandwidth budget); LRU eviction of non-scheduled
     requests' pages under pool pressure.
  3. batch — up to ``batch`` READY requests, least-served-first (CFS).
  4. decode — one paged+logged token per scheduled request (device op).
  5. compaction — when the log can't hold another step, coalesce it into
     resident pages (HBM) and parked pages (host tier), then swap-clear.

The host owns the engine's metadata. The page mapping (``page_table``,
``hbm_owner``, ``lru``) is written only here and sent to the device once a
step, before the decode, when it changed. The compaction watermarks, the
lengths, the log tail and the log's dirty pages are host copies of what the
device programs write, kept at the point where the engine launches each
write; every policy decision reads them and none reads the device.

Stats mirror the simulator's so the TPU runtime can be judged with the
paper's own metrics (coalescing ratio, switch count, fetch traffic), and
count the device->host reads the engine makes (``host_reads``): one token
per prefill at admission, one token read per step that decodes.

While a profile is recorded, ``step`` marks each phase with a host span
(``tiered.compact``, ``tiered.residency``, ``tiered.promote``,
``tiered.decode``, ``tiered.lru``) on the profiler's clock, which the
device's events share; outside a profile the spans record nothing.

The decode step and the compaction are compiled ahead of time when the
engine is built (``compile_seconds`` records the set-up time of each, and
``step_fn``/``compact_fn`` are the compiled programs, named
``jit_step`` and ``jit_compact_log``); their kernel path follows the
platform (``tiering.kernel_mode``).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import tiering
from repro.core.tiering import TieredKVConfig, host_slot
from repro.models.api import ModelSpec


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int
    out: List[int] = dataclasses.field(default_factory=list)
    served: int = 0  # CFS accounting
    done: bool = False


@dataclasses.dataclass
class ServeStats:
    steps: int = 0
    decoded_tokens: int = 0
    parks: int = 0  # coordinated context switches
    promoted_pages: int = 0
    evicted_pages: int = 0
    compactions: int = 0
    flushed_pages: int = 0
    flushed_tokens: int = 0
    host_reads: int = 0  # device->host reads (TieredEngine._fetch)

    @property
    def coalesce_ratio(self) -> float:
        """Tokens coalesced per flushed page-write (the paper's write-
        amplification win: 1 page write per page_size-token window instead
        of per token)."""
        return self.flushed_tokens / max(self.flushed_pages, 1)


class TieredEngine:
    def __init__(self, spec: ModelSpec, params, kv_cfg: TieredKVConfig):
        self.spec = spec
        self.cfg = spec.cfg
        self.kv = kv_cfg
        self.params = params
        self.state = tiering.init_state(kv_cfg, spec.cfg, dtype=jnp.bfloat16)
        B = kv_cfg.batch
        i32 = jnp.int32
        # flush lists are padded to log_slots rows (each log entry dirties at
        # most one page), so one compaction program serves every flush
        flush = jax.ShapeDtypeStruct((kv_cfg.log_slots, 3), i32)
        self.compile_seconds: Dict[str, float] = {}
        self.step_fn = self._compile(
            "decode", jax.jit(tiering.build_paged_decode_step(spec, kv_cfg)),
            params, self.state,
            jax.ShapeDtypeStruct((B, 1), i32), jax.ShapeDtypeStruct((B,), i32),
        )
        # named for its program, "jit_compact_log" (a fresh function per
        # engine, as the step is, so each traces its own kernel path); the
        # pools are donated: compaction rewrites them in place
        def compact_log(state, flush_hbm, flush_host):
            return tiering.compact_log(kv_cfg, state, flush_hbm, flush_host)

        self.compact_fn = self._compile(
            "compact", jax.jit(compact_log, donate_argnums=0),
            self.state, flush, flush,
        )
        self.prefill_fn = jax.jit(spec.prefill)  # compiles once per length
        self.requests: Dict[int, Request] = {}
        # host-side metadata: the page mapping, owned here ...
        self.page_table = np.full(
            (kv_cfg.max_requests, kv_cfg.max_pages_per_req), -1, np.int32)
        self._table_changed = False  # since it was last sent to the device
        self.hbm_owner: List[Optional[tuple]] = [None] * kv_cfg.n_hbm_pages
        self.lru: np.ndarray = np.zeros(kv_cfg.n_hbm_pages, np.int64)
        # ... and copies of the device's watermarks, lengths and log tail,
        # with the log's dirty pages {(rid, logical): tokens} since the last
        # compaction (what log_meta holds)
        self.compacted = np.zeros(kv_cfg.max_requests, np.int32)
        self.lengths = np.zeros(kv_cfg.max_requests, np.int32)
        self.log_tail = 0
        self.dirty: Dict[tuple, int] = {}
        self.stats = ServeStats()
        self._clock = 0

    def _compile(self, name: str, fn, *args):
        t0 = time.perf_counter()
        compiled = fn.lower(*args).compile()
        self.compile_seconds[name] = time.perf_counter() - t0
        return compiled

    def _fetch(self, x):
        """Every device->host read of the engine, counted: the tokens of a
        prefill and of a decode step. The policy reads only host state."""
        self.stats.host_reads += 1
        return jax.device_get(x)

    # ---- admission ----
    def add_request(self, req: Request) -> None:
        assert len(self.requests) < self.kv.max_requests, "slots exhausted"
        max_pages = -(-(len(req.prompt) + req.max_new_tokens) // self.kv.page_size)
        assert max_pages <= self.kv.n_hbm_pages, (
            f"request needs up to {max_pages} pages > HBM pool "
            f"{self.kv.n_hbm_pages}; enlarge the pool or page size"
        )
        assert max_pages <= self.kv.max_pages_per_req, "max_pages_per_req too small"
        rid = req.rid
        self.requests[rid] = req
        prompt = jnp.asarray(req.prompt, jnp.int32)[None]
        logits, cache = self.prefill_fn(self.params, prompt)
        k = cache["k"][:, 0]  # (L, S, KV, hd)
        v = cache["v"][:, 0]
        # initial placement: prompt KV lands in the HOST tier (the paper's
        # "all data starts in the CXL-SSD")
        self.state = tiering.write_prefill_pages(
            self.kv, self.state, rid, k, v
        )
        self.lengths[rid] = self.compacted[rid] = len(req.prompt)
        # the prompt's next token comes from the prefill logits
        req.out.append(int(self._fetch(jnp.argmax(logits[0]))))
        req.served += 1
        self.stats.decoded_tokens += 1

    # ---- residency / promotion ----
    def _n_pages(self, rid: int) -> int:
        # attention reads pages only below the compaction watermark; newer
        # positions live in the (always-resident) write log
        return (int(self.compacted[rid]) + self.kv.page_size - 1) // self.kv.page_size

    def _missing(self, rid: int) -> List[int]:
        """The pages request ``rid`` needs that are not HBM-resident."""
        return np.flatnonzero(self.page_table[rid, :self._n_pages(rid)] < 0).tolist()

    def _free_slot(self, protect: set) -> Optional[int]:
        for s, owner in enumerate(self.hbm_owner):
            if owner is None:
                return s
        # LRU eviction among non-protected pages (clean by construction:
        # the log owns all un-flushed writes — the paper's key invariant)
        # stable: equal stamps evict in slot order on every host (numpy's
        # default sort breaks ties differently with the CPU's SIMD width)
        order = np.argsort(self.lru, kind="stable")
        for s in order:
            if self.hbm_owner[s] is not None and self.hbm_owner[s] not in protect:
                rid, logical = self.hbm_owner[s]
                self.page_table[rid, logical] = -1
                self._table_changed = True
                self.hbm_owner[s] = None
                self.stats.evicted_pages += 1
                return int(s)
        return None

    def _promote(self, rid: int, logical: int, protect: set) -> bool:
        slot = self._free_slot(protect)
        if slot is None:
            return False
        pairs = jnp.asarray([[host_slot(self.kv, rid, logical), slot]], jnp.int32)
        self.state["hbm_k"], self.state["hbm_v"] = tiering.copy_pages(
            self.state["hbm_k"], self.state["hbm_v"],
            self.state["host_k"], self.state["host_v"], pairs,
        )
        self.page_table[rid, logical] = slot
        self._table_changed = True
        self.hbm_owner[slot] = (rid, logical)
        self.lru[slot] = self._clock
        self.stats.promoted_pages += 1
        return True

    # ---- compaction ----
    def _compact(self) -> None:
        flush_hbm, flush_host = [], []
        for (rid, logical), ntok in sorted(self.dirty.items()):
            slot = int(self.page_table[rid, logical])
            if slot >= 0:
                flush_hbm.append([rid, logical, slot])
            # ALWAYS flush to the host backing store (write-back tier);
            # resident copies are updated in parallel (paper: cache updated
            # alongside the log so flushes need no merge read)
            flush_host.append([rid, logical, host_slot(self.kv, rid, logical)])
            self.stats.flushed_pages += 1
            self.stats.flushed_tokens += ntok
        self.state = self.compact_fn(
            self.state, self._flush_rows(flush_hbm), self._flush_rows(flush_host)
        )
        self.compacted[:] = self.lengths
        self.log_tail = 0
        self.dirty = {}
        self.stats.compactions += 1

    def _flush_rows(self, rows: List[List[int]]) -> jax.Array:
        out = np.full((self.kv.log_slots, 3), -1, np.int32)
        out[:, 1] = 0
        out[: len(rows)] = np.asarray(rows, np.int32).reshape(-1, 3)
        return jnp.asarray(out)

    # ---- one engine step ----
    def step(self) -> None:
        self._clock += 1
        active = [r for r in self.requests.values() if not r.done]
        if not active:
            return
        # 0. compact BEFORE the residency check: compaction advances the
        # watermark, which can create page demand — readiness must be
        # evaluated against the post-compaction layout
        if self.log_tail + self.kv.batch > self.kv.log_slots:
            with TraceAnnotation("tiered.compact"):
                self._compact()
        # 1. residency + parking (the coordinated context switch)
        with TraceAnnotation("tiered.residency"):
            ready, parked = [], []
            for r in active:
                missing = self._missing(r.rid)
                if missing:
                    parked.append((r, missing))
                else:
                    ready.append(r)
        # 2. promotion budget — closest-to-ready parked request first (SJF:
        # guarantees progress), just-promoted pages join the protect set so
        # the budget loop cannot evict its own work
        with TraceAnnotation("tiered.promote"):
            budget = self.kv.promote_pages_per_step
            protect = {(r.rid, p) for r in ready for p in range(self._n_pages(r.rid))}
            parked.sort(key=lambda rm: len(rm[1]))
            for r, missing in parked:
                self.stats.parks += 1
                for p in missing:
                    if budget <= 0:
                        break
                    if self._promote(r.rid, p, protect):
                        protect.add((r.rid, p))
                        budget -= 1
            if self._table_changed:
                # a copy: on the CPU backend the device array may alias the
                # host buffer, which later edits would then reach
                self.state["page_table"] = jnp.asarray(self.page_table.copy())
                self._table_changed = False
        # 3. schedule ready requests, least-served first (CFS)
        ready.sort(key=lambda r: r.served)
        batch = ready[: self.kv.batch]
        if not batch:
            return
        # 4. decode one token for the batch
        with TraceAnnotation("tiered.decode"):
            B = self.kv.batch
            req_ids = np.full((B,), -1, np.int32)
            tokens = np.zeros((B, 1), np.int32)
            for i, r in enumerate(batch):
                req_ids[i] = r.rid
                last = r.out[-1] if r.out else r.prompt[-1]
                tokens[i, 0] = last
            next_tok, updates = self.step_fn(
                self.params, self.state, jnp.asarray(tokens), jnp.asarray(req_ids)
            )
            self.state.update(updates)
            for r in batch:
                n = int(self.lengths[r.rid])
                key = (r.rid, n // self.kv.page_size)
                self.dirty[key] = self.dirty.get(key, 0) + 1
                self.lengths[r.rid] = n + 1
            self.log_tail += B
            next_np = self._fetch(next_tok)
        for i, r in enumerate(batch):
            r.out.append(int(next_np[i, 0]))
            r.served += 1
            if r.served >= r.max_new_tokens:
                r.done = True
            self.stats.decoded_tokens += 1
        # touch the LRU stamps of the scheduled requests' pages
        with TraceAnnotation("tiered.lru"):
            for r in batch:
                slots = self.page_table[r.rid, :self._n_pages(r.rid)]
                self.lru[slots[slots >= 0]] = self._clock
        self.stats.steps += 1

    def run(self, max_steps: int = 1000) -> ServeStats:
        for _ in range(max_steps):
            if all(r.done for r in self.requests.values()):
                break
            self.step()
        return self.stats
