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

from bench import check, control, harness
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
    cfg = harness.model_config(cell.config)
    prompt, out = list(range(1, 40)), [5, 6, 7]
    ref = check.reference_module(tiny_root, cell.config)
    from bench.weights import make

    params = make(cfg, 9)
    a = ref.served_logits(cell.config, params, prompt, out, bucket=64)
    b = ref.served_logits(cell.config, params, prompt, out, bucket=256)
    assert float(abs(a - b).max()) < 1e-4


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
