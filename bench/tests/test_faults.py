"""A whole run at a tiny size on the CPU, with the timed path broken
underneath: ``correct`` must come out false for each fault a serving
cell can have, and true for the sound program. The harness's look for a
chip is skipped (the run goes through ``serve_and_check``). Faults that
belong to training (half of a batch left out) or to several chips (the
exchange between them left out) have no counterpart in these one-chip
serving cells.

The control (the reference in fp8, ``reference.forward(low=True)``)
reads far above the sound program here as on the chip.
"""
import json
import time
from pathlib import Path

import jax.numpy as jnp

from bench import harness

BENCH = Path(__file__).resolve().parents[1]
PEAKS = json.loads((BENCH / "peaks.json").read_text())["devices"]["TPU v5 lite"]
# The tiny program runs in float32, as the reference does: their argmaxes
# differ only at ties within float32 rounding of the logits (about 1e-7
# of logits near 0.02 here), so 1e-5 leaves room; a fault moves a
# served token by a whole logit gap (above 1e-3 here).
LIMITS = {"plane_mismatch": 0, "logit_gap_partial": 1e-5, "logit_gap_full": 1e-5}
GAPS = ("logit_gap_partial", "logit_gap_full")


def tiny_cell(stream: bool, dtype: str = "float32"):
    cfg = json.loads((BENCH / "configs" / "olmo1b.json").read_text())
    cfg.update(n_layers=2, d_model=64, n_heads=4, n_kv=2, head_dim=16, d_ff=128,
               vocab=256, dtype=dtype)
    mix = json.loads((BENCH / "traffic" / "chat_coldstart.json").read_text())
    mix.update(slots=4, max_len=96, arrivals={"kind": "poisson", "rate_per_s": 4.0, "at_start": 4},
               prompt_tokens={"median": 20, "sigma": 0.5, "min": 4, "max": 48},
               output_tokens={"median": 8, "sigma": 0.5, "min": 2, "max": 24},
               sample_requests=40)
    mix["stream"] = ({"rate_bytes_per_s": 800000, "chunk_bytes": 16384, "latency_s": 0.005}
                     if stream else None)
    return cfg, mix


def run(stream=True, control=False, dtype="float32"):
    cfg, mix = tiny_cell(stream, dtype)
    return harness.serve_and_check(cfg, mix, LIMITS, [], 2**31 + 5, 3.0, False,
                                   time.perf_counter(), peaks=PEAKS, control=control,
                                   log=lambda m: None)


def test_sound_program_is_correct():
    res = run()
    assert res["correct"], res["checks"]
    assert res["checks"]["plane_mismatch"]["value"] == 0
    assert res["checks"]["uncompared"]["value"] == 0
    # the stream lands inside the window: tokens served at both kinds of stage
    assert set(GAPS) <= set(res["checks"]), res["checks"]


def gap(checks) -> float:
    return max(checks[g]["value"] for g in GAPS if g in checks)


def test_token_altered_where_produced(monkeypatch):
    from repro.models.model import Model

    real = Model.decode_step

    def altered(self, params, caches, tokens, pos):
        logits, caches = real(self, params, caches, tokens, pos)
        return jnp.roll(logits, 1, axis=-1), caches

    monkeypatch.setattr(Model, "decode_step", altered)
    res = run(stream=False)
    print("altered token gap", gap(res["checks"]))
    assert not res["correct"]
    assert res["checks"]["logit_gap_full"]["value"] > LIMITS["logit_gap_full"]


def test_decode_returns_its_cache_unchanged(monkeypatch):
    from repro.models.model import Model

    real = Model.decode_step

    def stale(self, params, caches, tokens, pos):
        logits, _ = real(self, params, caches, tokens, pos)
        return logits, caches

    monkeypatch.setattr(Model, "decode_step", stale)
    res = run(stream=False)
    print("stale cache gap", gap(res["checks"]))
    assert not res["correct"]


def test_plane_or_leaves_the_accumulator_unchanged(monkeypatch):
    from repro.core.plane_store import PlaneStore

    def no_or(self, items):
        for idx in items:
            self.received[idx] += 1
            self._dirty.add(idx)
            self._qleaf_cache.pop(self.slots[idx].key, None)
            self._leaf_cache.pop(self.slots[idx].key, None)

    monkeypatch.setattr(PlaneStore, "_ingest_round", no_or)
    res = run()
    assert not res["correct"]
    assert res["checks"]["plane_mismatch"]["value"] > 0


def test_control_comes_out_not_correct():
    """The control (the reference with fp8 products) in the program's
    place, judged by the same comparison and limit: ``correct`` is false.
    The program as configured (bfloat16 activations) stays within it."""
    res = run(stream=False, control=True, dtype="bfloat16")
    checks = res["checks"]
    print("program", checks["program_logit_gap_full"]["value"],
          "control", checks["logit_gap_full"]["value"])
    assert not res["correct"]
    assert checks["logit_gap_full"]["value"] > checks["logit_gap_full"]["limit"]
    assert checks["logit_gap_full"]["value"] >= 3 * checks["program_logit_gap_full"]["value"]
