"""What the tiered engine shows of its host policy: the count of its
device->host reads (``ServeStats.host_reads``), its phase spans in a
profile, and the names of its device programs.

The read count is checked against a count worked out from the engine's
state before each step, not with ``jax.transfer_guard_device_to_host``:
on the CPU backend the guard lets implicit reads (``int(x)``,
``np.asarray(x)``) through.
"""
import jax
import pytest
from jax.profiler import TraceAnnotation

from repro.configs import get_reduced
from repro.core.tiering import TieredKVConfig
from repro.models.api import ModelSpec
from repro.serving.engine import Request, TieredEngine

PHASES = ("tiered.compact", "tiered.residency", "tiered.promote",
          "tiered.decode", "tiered.lru")
PROMPTS = {0: list(range(7, 27)), 1: list(range(40, 75)), 2: list(range(5, 18))}
CASES = {  # the "compaction" and "pool_pressure" cases of test_tiering.py
    "compaction": TieredKVConfig(page_size=8, n_hbm_pages=32, max_requests=4,
                                 max_pages_per_req=12, log_slots=8, batch=2,
                                 promote_pages_per_step=8),
    "pool_pressure": TieredKVConfig(page_size=8, n_hbm_pages=16, max_requests=4,
                                    max_pages_per_req=12, log_slots=32, batch=2,
                                    promote_pages_per_step=2),
}


@pytest.fixture(scope="module")
def model():
    spec = ModelSpec(get_reduced("qwen3-1.7b"))
    return spec, spec.init(jax.random.PRNGKey(0))


def engine(model, kv, n_new=20):
    eng = TieredEngine(*model, kv)
    for rid, p in PROMPTS.items():
        eng.add_request(Request(rid=rid, prompt=p, max_new_tokens=n_new))
    return eng


def reads_before_step(eng):
    """The reads the next step makes, from the engine's host state before
    it: the policy reads only host state, so the step reads its tokens if
    it decodes, which it does iff a request is ready once a compaction due
    now has moved the watermarks. Returns that count, whether the step
    compacts, the ready requests and the pages each active request needs."""
    kv = eng.kv
    active = [r for r in eng.requests.values() if not r.done]
    compacts = eng.log_tail + kv.batch > kv.log_slots
    # compaction moves the watermark to the end
    compacted = eng.lengths if compacts else eng.compacted
    pages = {r.rid: -(-int(compacted[r.rid]) // kv.page_size) for r in active}
    ready = {r.rid for r in active
             if (eng.page_table[r.rid, :pages[r.rid]] >= 0).all()}
    return int(bool(ready)), compacts, ready, pages


@pytest.mark.parametrize("case", list(CASES))
def test_host_reads_counted_on_every_step(model, case):
    eng = engine(model, CASES[case])
    assert eng.stats.host_reads == len(PROMPTS)  # one token read per prefill
    compaction_steps = parked_steps = 0
    while not all(r.done for r in eng.requests.values()):
        want, compacts, ready, pages = reads_before_step(eng)
        served = {rid: len(r.out) for rid, r in eng.requests.items()}
        before = eng.stats.host_reads
        eng.step()
        scheduled = [rid for rid, r in eng.requests.items() if len(r.out) > served[rid]]
        assert set(scheduled) <= ready
        assert bool(scheduled) == bool(ready)
        assert eng.stats.host_reads - before == want, eng.stats.steps
        compaction_steps += compacts
        parked_steps += len(ready) < len(pages)
    assert compaction_steps == eng.stats.compactions > 0
    if case == "pool_pressure":
        assert parked_steps > 0


def _spans(profile_dir):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(next(profile_dir.rglob("*.xplane.pb"))))
    out = []
    for plane in data.planes:
        if plane.name == "/host:CPU":
            out += [(ev.name, int(ev.start_ns), int(ev.end_ns))
                    for line in plane.lines for ev in line.events
                    if ev.name.startswith(("tiered.", "test."))]
    return sorted(out, key=lambda s: s[1])


def test_phase_spans_of_a_compaction_step(model, tmp_path):
    """A profile of one step that compacts and decodes holds each phase
    span once, in the order the step runs them, inside the caller's span."""
    eng = engine(model, CASES["compaction"])
    while True:
        _, compacts, ready, _ = reads_before_step(eng)
        if compacts and ready:
            break
        assert not all(r.done for r in eng.requests.values())
        eng.step()
    compactions = eng.stats.compactions
    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("test.step"):
        eng.step()
    jax.profiler.stop_trace()
    assert eng.stats.compactions == compactions + 1
    spans = _spans(tmp_path)
    (outer,) = [s for s in spans if s[0] == "test.step"]
    inner = [s for s in spans if s[0] != "test.step"]
    assert [s[0] for s in inner] == list(PHASES)
    assert all(outer[1] <= a < b <= outer[2] for _, a, b in inner)
    assert all(b <= c for (_, _, b), (_, c, _) in zip(inner, inner[1:]))


def test_device_programs_carry_their_names(model):
    eng = engine(model, CASES["compaction"], n_new=2)
    assert eng.compact_fn.as_text().startswith("HloModule jit_compact_log")
    assert eng.step_fn.as_text().startswith("HloModule jit_step")
    assert eng.promote_fn.as_text().startswith("HloModule jit_promote_pages")
