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


def _root(tmp_path, config, reference=None):
    """A checkout-like root whose BENCHMARK.json names one small cell,
    "<config>.tiny-mix", whose configuration, traffic mix and limit (and
    reference module, where given) exist only as files added to a copy of
    bench/ (as a later PR would add them), next to a per-layer metric
    "served_per_step" that exists the same way."""
    bench = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    cell = f"{config}.tiny-mix"
    shutil.copy(DATA / f"{config}.json", bench / "configs" / f"{config}.json")
    shutil.copy(DATA / "tiny-mix.json", bench / "traffic" / "tiny-mix.json")
    shutil.copy(DATA / f"{cell}.limits.json", bench / "limits" / f"{cell}.json")
    if reference:
        shutil.copy(DATA / f"{reference}.py", bench / "reference" / f"{reference}.py")
    (bench / "metrics" / "served_per_step.py").write_text(
        '"""Tokens decoded per window step."""\n\n\n'
        "def read(run):\n"
        "    return run.tokens / len(run.steps) if run.steps else None\n")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": config, "source": f"bench/tests/data/{config}.json",
                            "file": f"bench/configs/{config}.json", "reduced": [],
                            "why": "CPU tests"})
    spec["workloads"].append({"name": cell, "config": config,
                              "traffic": "tiny-mix", "chips": 1, "why": "CPU tests"})
    for m in spec["end_to_end"]:
        m.get("workloads", []).append(cell)
    spec["per_layer"].append({"name": "served_per_step", "unit": "tokens/step",
                              "better": "higher", "source": "host_clock",
                              "layer": "host policy (serving/engine.py)",
                              "moves": "decode_tok_s", "workloads": [cell]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp_path


@pytest.fixture()
def tiny_root(tmp_path):
    """`_root` with cell "tiny.tiny-mix", a dense GQA configuration that the
    benchmark's own reference module serves."""
    return _root(tmp_path, "tiny")


@pytest.fixture()
def tiny_moe_root(tmp_path):
    """`_root` with cell "tiny-moe.tiny-mix", a mixture-of-experts
    configuration (family "moe") whose reference module, with its own leaf
    table and FLOP count, is one of the added files."""
    return _root(tmp_path, "tiny-moe", reference="tiny_moe")
