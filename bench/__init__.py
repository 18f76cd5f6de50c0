"""The benchmark: ``python3 bench/run.py --workload <cell> ...`` (see run.py)."""
from __future__ import annotations

import functools
import importlib.util
from pathlib import Path


@functools.cache
def load_file(path: Path, prefix: str):
    """The Python file at ``path`` as a module named ``<prefix><stem>``,
    executed once per process."""
    spec = importlib.util.spec_from_file_location(f"{prefix}{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
