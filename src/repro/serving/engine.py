"""Continuous-batching serving engine with the SkyByte scheduler.

The engine is the OS half of the co-design: it owns policy (who runs,
what gets promoted/evicted, when the log compacts) while core/tiering.py
owns the device data path — mirroring the paper's host-OS / SSD-controller
split.

Per decode step:
  1. residency check — a request is READY iff all its KV pages are in the
     HBM pool. A request with any page not resident is PARKED (the
     coordinated context switch) and its pages are queued for promotion.
  2. promotion — up to ``promote_pages_per_step`` host->HBM page copies
     (the migration bandwidth budget); LRU eviction of non-scheduled
     requests' pages under pool pressure. The policy picks every page and
     slot on the host; one device program then copies the step's pages.
  3. batch — up to ``batch`` READY requests, least-served-first (CFS).
  4. decode — one paged+logged token per scheduled request (device op).
  5. compaction — when the log can't hold another step, coalesce it into
     resident pages (HBM) and parked pages (host tier), then swap-clear.

The host is the only owner of the engine's metadata: the page mapping
(``page_table``, ``hbm_owner``, ``lru``), the lengths, the compaction
watermarks (``compacted``), the log's tail and its index (``log_meta``:
owner and position of each log slot). No device array holds any of them;
the device state is the KV pools and the log. The decode step is handed
the rows of its batch and the log index (two host->device transfers), the
compaction the log index and its flush rows; every policy decision reads
host state and none reads the device.

Stats mirror the simulator's so the TPU runtime can be judged with the
paper's own metrics (coalescing ratio, switch count, fetch traffic), and
count the device->host reads the engine makes (``host_reads``): one token
per prefill at admission, one token read per step that decodes.

While a profile is recorded, ``step`` marks each phase with a host span
(``tiered.compact``, ``tiered.residency``, ``tiered.promote``,
``tiered.decode``, ``tiered.lru``) on the profiler's clock, which the
device's events share; outside a profile the spans record nothing.

The decode step, the compaction and the promotion copy are compiled ahead
of time when the engine is built (``compile_seconds`` records the set-up
time of each, and ``step_fn``/``compact_fn``/``promote_fn`` are the
compiled programs, named ``jit_step``, ``jit_compact_log`` and
``jit_promote_pages``); their kernel path follows the platform
(``tiering.kernel_mode``).
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
    promote_calls: int = 0  # promotion programs launched (one a step at most)
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
        log_meta = jax.ShapeDtypeStruct((kv_cfg.log_slots, 2), i32)
        self.step_fn = self._compile(
            "decode", jax.jit(tiering.build_paged_decode_step(spec, kv_cfg)),
            params, self.state,
            jax.ShapeDtypeStruct((B, tiering.TABLE + kv_cfg.max_pages_per_req), i32),
            log_meta,
        )
        # named for its program, "jit_compact_log" (a fresh function per
        # engine, as the step is, so each traces its own kernel path); the
        # four pools are donated: compaction rewrites them in place
        def compact_log(*args):
            return tiering.compact_log(*args)

        st = self.state
        self.compact_fn = self._compile(
            "compact", jax.jit(compact_log, donate_argnums=(0, 1, 2, 3)),
            st["hbm_k"], st["hbm_v"], st["host_k"], st["host_v"],
            st["log_k"], st["log_v"], log_meta, flush, flush,
        )
        # "jit_promote_pages": one launch copies a step's promoted pages
        # (padded to the budget) into the HBM pools, donated, so in place
        self.promote_fn = self._compile(
            "promote", jax.jit(tiering.promote_pages, donate_argnums=(0, 1)),
            st["hbm_k"], st["hbm_v"], st["host_k"], st["host_v"],
            jax.ShapeDtypeStruct((kv_cfg.promote_pages_per_step, 2), i32),
            jax.ShapeDtypeStruct((), i32),
        )
        self.prefill_fn = jax.jit(spec.prefill)  # compiles once per length
        self.requests: Dict[int, Request] = {}
        # the metadata, owned here alone: the page mapping; the lengths and
        # compaction watermarks (positions below the watermark live in
        # pages, the rest in the log); the log's tail and its index, the
        # (owner, position) of each slot, -1 from the tail on
        self.page_table = np.full(
            (kv_cfg.max_requests, kv_cfg.max_pages_per_req), -1, np.int32)
        self.hbm_owner: List[Optional[tuple]] = [None] * kv_cfg.n_hbm_pages
        self.lru: np.ndarray = np.zeros(kv_cfg.n_hbm_pages, np.int64)
        self.lengths = np.zeros(kv_cfg.max_requests, np.int32)
        self.compacted = np.zeros(kv_cfg.max_requests, np.int32)
        self.log_tail = 0
        self.log_meta = np.full((kv_cfg.log_slots, 2), -1, np.int32)
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
                self.hbm_owner[s] = None
                self.stats.evicted_pages += 1
                return int(s)
        return None

    def _promote(self, rid: int, logical: int, protect: set,
                 copies: List[tuple]) -> bool:
        """Map the page to a free (or evicted) HBM slot and queue its copy
        in ``copies`` as (host slot, HBM slot); ``_copy_pages`` runs them."""
        slot = self._free_slot(protect)
        if slot is None:
            return False
        copies.append((host_slot(self.kv, rid, logical), slot))
        self.page_table[rid, logical] = slot
        self.hbm_owner[slot] = (rid, logical)
        self.lru[slot] = self._clock
        self.stats.promoted_pages += 1
        return True

    def _copy_pages(self, copies: List[tuple]) -> None:
        """One launch of the promotion program for the step's copies. Their
        HBM slots are distinct (each promoted page joins the protect set, so
        no slot is taken twice in a step), so the order is free."""
        pairs = np.full((self.kv.promote_pages_per_step, 2), -1, np.int32)
        pairs[: len(copies)] = copies
        st = self.state
        st["hbm_k"], st["hbm_v"] = self.promote_fn(
            st["hbm_k"], st["hbm_v"], st["host_k"], st["host_v"],
            jnp.asarray(pairs), np.int32(len(copies)),
        )
        self.stats.promote_calls += 1

    # ---- compaction ----
    def _compact(self) -> None:
        # the log's dirty pages, ascending (rid, logical), and their tokens
        live = self.log_meta[self.log_meta[:, 0] >= 0]
        pages, ntok = np.unique(
            np.stack([live[:, 0], live[:, 1] // self.kv.page_size], axis=1),
            axis=0, return_counts=True)
        rid, logical = pages[:, 0], pages[:, 1]
        slot = self.page_table[rid, logical]
        # ALWAYS flush to the host backing store (write-back tier);
        # resident copies are updated in parallel (paper: cache updated
        # alongside the log so flushes need no merge read)
        flush_hbm = np.stack([rid, logical, slot], axis=1)[slot >= 0]
        flush_host = np.stack([rid, logical, host_slot(self.kv, rid, logical)], axis=1)
        self.stats.flushed_pages += len(pages)
        self.stats.flushed_tokens += int(ntok.sum())
        st = self.state
        st["hbm_k"], st["hbm_v"], st["host_k"], st["host_v"] = self.compact_fn(
            st["hbm_k"], st["hbm_v"], st["host_k"], st["host_v"],
            st["log_k"], st["log_v"], jnp.asarray(self.log_meta.copy()),
            self._flush_rows(flush_hbm), self._flush_rows(flush_host),
        )
        self.compacted[:] = self.lengths
        self.log_tail = 0
        self.log_meta[:] = -1
        self.stats.compactions += 1

    def _flush_rows(self, rows: np.ndarray) -> jax.Array:
        out = np.full((self.kv.log_slots, 3), -1, np.int32)
        out[:, 1] = 0
        out[: len(rows)] = rows
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
            copies: List[tuple] = []
            for r, missing in parked:
                self.stats.parks += 1
                for p in missing:
                    if budget <= 0:
                        break
                    if self._promote(r.rid, p, protect, copies):
                        protect.add((r.rid, p))
                        budget -= 1
            if copies:
                self._copy_pages(copies)
        # 3. schedule ready requests, least-served first (CFS)
        ready.sort(key=lambda r: r.served)
        batch = ready[: self.kv.batch]
        if not batch:
            return
        # 4. decode one token for the batch
        with TraceAnnotation("tiered.decode"):
            rids = np.array([r.rid for r in batch], np.int32)
            tail, n = self.log_tail, len(rids)
            rows = tiering.batch_rows(self.kv, [r.out[-1] for r in batch], rids,
                                      self.lengths, self.compacted, self.page_table, tail)
            # the log index gains the real rows' (owner, position)
            self.log_meta[tail:tail + n] = rows[:n, [tiering.REQ, tiering.LENGTH]]
            # a copy: on the CPU backend the device array may alias the
            # host buffer, which later edits would then reach
            st = self.state
            next_tok, st["log_k"], st["log_v"] = self.step_fn(
                self.params, st, jnp.asarray(rows), jnp.asarray(self.log_meta.copy()))
            self.lengths[rids] += 1
            self.log_tail += self.kv.batch
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
