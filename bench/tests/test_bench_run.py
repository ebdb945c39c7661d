"""The harness end to end on the CPU at a small size: a cell added as files
only, the correctness check, its control and a fault planted in the timed
path, and the command's refusals."""
import io
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from bench import control, flops, harness, refs, weights
from bench.tests.conftest import ROOT

CELL = "tiny.tiny-mix"


def _run(root, seed):
    cell = harness.load_cell(root, CELL)
    return harness.run(cell, seed, 1.0, False, time.perf_counter(), log=io.StringIO())


def test_a_cell_added_as_files_runs_and_is_correct(tiny_root):
    cell = harness.load_cell(tiny_root, CELL)
    assert cell.mix["requests"]["new_tokens"] == 8
    assert "served_per_step" in [m["name"] for m in cell.per_layer]
    read = harness.load_metric(tiny_root, "served_per_step")
    steps = [harness.Step(0.0, 1.0, [40, 70]), harness.Step(1.0, 2.0, [41])]
    rec = harness.RunRecord(cell, steps, (0.0, 2.0), {}, {}, {})
    assert read(rec) == 1.5
    res = _run(tiny_root, 3)
    assert res["correct"] is True and res["failed"] == 0
    assert list(res)[-1] == "check"
    assert res["check"]["max_logit_gap"]["limit"] == 0.045
    assert set(res["metrics"]) == {"decode_tok_s", "itl_p90_ms", "setup_s"}
    assert res["metrics"]["decode_tok_s"]["value"] > 0


def test_the_float8_control_fails_the_limit(tiny_root):
    cell = harness.load_cell(tiny_root, CELL)
    r = control.readings(cell, 4, 1.0, log=io.StringIO())
    limit = cell.limits["max_logit_gap"]["limit"]
    assert r["program"] <= limit < r["control"]


def test_a_token_altered_where_it_is_produced_fails_the_check(tiny_root, monkeypatch):
    from repro.serving.engine import TieredEngine

    step = TieredEngine.step

    def altered(self):
        before = {rid: len(r.out) for rid, r in self.requests.items()}
        step(self)
        for rid, r in self.requests.items():
            if len(r.out) > before[rid]:
                r.out[-1] = (r.out[-1] + 1) % self.cfg.vocab
                return

    monkeypatch.setattr(TieredEngine, "step", altered)
    res = _run(tiny_root, 3)
    assert res["correct"] is False and res["failed"] >= 1
    assert res["check"]["max_logit_gap"]["value"] > 0.045


def test_the_reference_ignores_padding_after_a_position(tiny_root):
    cell = harness.load_cell(tiny_root, CELL)
    prompt, out = list(range(1, 40)), [5, 6, 7]
    ref = refs.load(tiny_root, cell.config)
    from bench.weights import make

    params = make(cell.config, 9, root=tiny_root)
    a = ref.served_logits(cell.config, params, prompt, out, bucket=64)
    b = ref.served_logits(cell.config, params, prompt, out, bucket=256)
    assert float(abs(a - b).max()) < 1e-4


MOE = "tiny-moe.tiny-mix"


def test_a_configuration_of_another_family_added_as_files_is_correct(tiny_moe_root):
    """A mixture-of-experts configuration whose reference module (leaf
    table, logits, FLOP count) is one of its added files."""
    cell = harness.load_cell(tiny_moe_root, MOE)
    assert harness.model_config(cell.config).moe.num_experts == 4
    params = weights.make(cell.config, 3, root=tiny_moe_root)
    assert params["blocks"]["we_gate"].shape == (2, 4, 256, 256)
    assert "w_gate" not in params["blocks"]
    res = harness.run(cell, 3, 1.0, False, time.perf_counter(), log=io.StringIO())
    assert res["correct"] is True and res["failed"] == 0
    assert res["check"]["max_logit_gap"]["limit"] == cell.limits["max_logit_gap"]["limit"]


def test_the_float8_control_fails_the_moe_limit(tiny_moe_root):
    cell = harness.load_cell(tiny_moe_root, MOE)
    r = control.readings(cell, 4, 1.0, log=io.StringIO())
    limit = cell.limits["max_logit_gap"]["limit"]
    assert r["program"] <= limit < r["control"]


def test_decode_mfu_uses_the_references_count(tiny_moe_root):
    cell = harness.load_cell(tiny_moe_root, MOE)
    ref = refs.load(tiny_moe_root, cell.config)
    steps = [harness.Step(0.0, 1.0, [40, 70]), harness.Step(1.0, 2.0, [41])]
    peaks = {"bf16_flops_per_s": 197e12}
    rec = harness.RunRecord(cell, steps, (0.0, 2.0), {}, {"busy_ns": 50_000}, peaks)
    own = sum(ref.decode_token_flops(cell.config, c) for c in (40, 70, 41))
    dense = sum(flops.decode_token_flops(dict(cell.config, reference="dense_gqa"), c)
                for c in (40, 70, 41))
    assert own != dense
    read = harness.load_metric(tiny_moe_root, "decode_mfu")
    assert read(rec) == pytest.approx(100 * own / (50e-6 * 197e12))


def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen3-1.7b.docqa-pressure",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_the_command_refuses_a_cpu():
    p = _command(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_the_command_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _command(tmp_path)
    assert p.returncode != 0 and not p.stdout.strip()
    assert json.loads((tmp_path / "BENCHMARK.json").read_text())["paths"] == ["bench"]
