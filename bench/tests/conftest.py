"""Fixtures of the benchmark's CPU tests."""
import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture()
def tiny_root(tmp_path):
    """A checkout-like root whose BENCHMARK.json names one small cell,
    "tiny.tiny-mix", whose configuration, traffic mix and limit exist only as
    files added to a copy of bench/ (as a later PR would add them), next to
    a per-layer metric "served_per_step" that exists the same way."""
    bench = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(DATA / "tiny.json", bench / "configs" / "tiny.json")
    shutil.copy(DATA / "tiny-mix.json", bench / "traffic" / "tiny-mix.json")
    shutil.copy(DATA / "tiny.tiny-mix.limits.json",
                bench / "limits" / "tiny.tiny-mix.json")
    (bench / "metrics" / "served_per_step.py").write_text(
        '"""Tokens decoded per window step."""\n\n\n'
        "def read(run):\n"
        "    return run.tokens / len(run.steps) if run.steps else None\n")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny", "source": "bench/tests/data/tiny.json",
                            "file": "bench/configs/tiny.json", "reduced": [],
                            "why": "CPU tests"})
    spec["workloads"].append({"name": "tiny.tiny-mix", "config": "tiny",
                              "traffic": "tiny-mix", "chips": 1, "why": "CPU tests"})
    for m in spec["end_to_end"]:
        m.get("workloads", []).append("tiny.tiny-mix")
    spec["per_layer"].append({"name": "served_per_step", "unit": "tokens/step",
                              "better": "higher", "source": "host_clock",
                              "layer": "host policy (serving/engine.py)",
                              "moves": "decode_tok_s", "workloads": ["tiny.tiny-mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp_path
