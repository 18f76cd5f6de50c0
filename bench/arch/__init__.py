"""One file per architecture family: all of the benchmark that depends on the block.

A configuration file (``bench/configs/<cfg>.json``) names its family
with the key ``"bench_arch"``, and ``load`` finds
``bench/arch/<bench_arch>.py`` by path, as the harness finds a metric's
reader. A configuration without the key, or naming no file here, is an
error. A new architecture comes to the benchmark as a new file here;
the harness, the reference's quantizer and the counts' arithmetic stay
as they are.

Each file defines these six functions, of a configuration ``cfg``
(the parsed JSON file), and may keep helpers of its own:

- ``arch_fields(cfg) -> dict``: the fields of the program's ``ArchConfig``
  that the configuration overrides on its ``program_config`` (the
  harness adds ``dtype`` and ``param_dtype``).
- ``leaf_specs(cfg) -> {path: (shape, mean, std)}``: every parameter
  leaf the benchmark draws from the seed, as a normal of that mean and
  std (``weights.make_flat`` draws them in one jitted call).
- ``to_program_tree(cfg, flat) -> params``: those leaves nested into the
  parameter tree the program serves.
- ``forward(cfg, raw, lohi, m, tokens, *, low=False, stage_of=None)``:
  the plain float32 reference, ``(T, V)`` logits of one sequence, each
  position computed at ``m[stage_of[t]]`` received bits
  (``reference.Stages`` does that selection and the eq.-(5) weights).
  It imports nothing of the program. With ``low=True`` every matrix
  product takes fp8-rounded operands (each goes through ``Stages.mm``
  or ``Stages.dot``, which do that): that is the control, which must
  come out not correct.
- ``kv_bytes_per_position(cfg) -> int``: the cache bytes one position
  holds across the layers (``flash_decode_roofline`` reads it).
- ``sequence_flops(cfg, start, stop) -> float``: model operations to
  process the tokens at positions ``start .. stop - 1`` of one sequence
  (``step_mfu`` reads it).
"""
from __future__ import annotations

import re
from pathlib import Path

from bench import load_file

ROOT = Path(__file__).resolve().parent
KEY = "bench_arch"
FUNCTIONS = ("arch_fields", "leaf_specs", "to_program_tree", "forward",
             "kv_bytes_per_position", "sequence_flops")
_NAME = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")


def load(cfg: dict):
    """The architecture file that configuration ``cfg`` names, from
    ``ROOT`` (this directory)."""
    name = cfg.get(KEY)
    if name is None:
        raise ValueError(f"configuration {cfg.get('name')!r} has no {KEY!r} key: "
                         f"name its file under bench/arch/")
    if not isinstance(name, str) or not _NAME.fullmatch(name):
        raise ValueError(f"{KEY} {name!r} is not a file name under bench/arch/")
    path = ROOT / f"{name}.py"
    if not path.is_file():
        known = sorted(p.stem for p in ROOT.glob("*.py") if p.stem != "__init__")
        raise ValueError(f"{KEY} {name!r}: no file {path}; known: {known}")
    mod = load_file(path, "bench_arch_")
    missing = [f for f in FUNCTIONS if not callable(getattr(mod, f, None))]
    if missing:
        raise ValueError(f"{KEY} file {path} does not define {missing}")
    return mod
