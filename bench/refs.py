"""A configuration's plain reference module: the file
`bench/reference/<config["reference"]>.py` under a checkout's root, loaded
by its path (so a configuration added as files brings its own) and once a
process. What such a module defines is the configuration contract in
`bench/harness.py`'s docstring."""
from __future__ import annotations

import functools
import importlib.util
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def load(root: Path, config: dict):
    return _load(str(Path(root) / "bench" / "reference" / f"{config['reference']}.py"))


@functools.cache
def _load(path: str):
    spec = importlib.util.spec_from_file_location(f"ref_{Path(path).stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def own(root: Path, config: dict, name: str):
    """The reference module's function `name`, or None where it has none."""
    return getattr(load(root, config), name, None)
