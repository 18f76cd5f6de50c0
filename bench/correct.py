"""The comparison that decides ``correct``.

Run after the window has closed and the program's state is freed, from
weights made again from the seed:

- ``plane_mismatch`` (cells that stream the wire): for every stage the
  engine served, the per-tensor checksum of the client's accumulators
  taken when the stage was applied, against the reference's codes
  (eq. 2) truncated to that stage. Exact: the limit is 0.
- ``logit_gap_partial`` and ``logit_gap_full``: for a sample drawn from
  the seed of the requests that were served tokens (finished, or still
  decoding when the window closed: every token the client was handed),
  the longest always in it, the widest gap by which a served token's
  logit lies below the reference's best, the reference reading the
  prompt and the served tokens once, each position at the stage the
  server held when it computed it (the engine's ``admit_stage`` for the
  prompt, ``stage_log`` for each decoded position). A request qualifies
  when its whole prompt was consumed at one stage: its admission stage
  is the stage of its first decode step (the stage of each prompt chunk
  is not observable otherwise). Each served token is judged by the stage
  of the position it was computed at: ``full`` at the last stage (every
  plane received), ``partial`` at any earlier one, each against a limit
  of its own, since the gaps of both the program and the control are
  wider on coarser weights (PERF.md section 2). A group with no served
  token is not reported.
- ``uncompared``: 1 when no served token could be compared.

With ``control`` the fp8 reference (``reference.forward(low=True)``) takes
the program's place: at each position of the same prompts and served
tokens, the token it puts first is judged as ``logit_gap_<group>``
against the same limit, so ``correct`` comes out false; the program's
own reading is kept beside it as ``program_logit_gap_<group>``.
"""
from __future__ import annotations

import numpy as np

from bench import reference, weights
from bench.traffic import rng_for


def stage_bits(cfg: dict, stage: int) -> int:
    return int(sum(cfg["plane_widths"][:stage]))


def sample(cfg, mix, seed, served, admit, stage_log, prompts) -> list[int]:
    """Requests with served tokens that qualify, the longest first, then
    a sample drawn from the seed."""
    ok = [rid for rid in sorted(served)
          if served[rid] and rid in prompts and admit.get(rid) == stage_log.get(rid, [None])[0]]
    if not ok:
        return []
    size = lambda r: len(prompts[r]) + len(served[r])  # noqa: E731
    longest = max(ok, key=lambda r: (size(r), -r))
    rest = [r for r in ok if r != longest]
    k = min(len(rest), mix["sample_requests"] - 1)
    picked = rng_for(seed, 3).choice(len(rest), size=k, replace=False) if k else []
    return [longest] + [rest[i] for i in sorted(picked)]


def check(cfg, mix, limits, seed, served, admit, stage_log, *,
          prompts, checksums, checksum_keys, control=False, log=print) -> dict:
    import jax

    raw = weights.make_flat(cfg, seed)
    lohi = jax.jit(lambda r: {k: reference.leaf_range(v) for k, v in r.items()})(raw)
    out = {}

    if checksums:
        bits = cfg["bits"]
        cs = jax.jit(lambda x, lo, hi, m: reference.checksum(
            reference.truncate(reference.codes(x, lo, hi, bits), bits, m)))
        bad = 0
        for stage, prog in checksums:
            m = stage_bits(cfg, stage)
            for i, key in enumerate(checksum_keys):
                want = np.asarray(cs(raw[key], *lohi[key], np.int32(m)))
                if not np.array_equal(want, prog[i]):
                    bad += 1
                    log(f"  plane store: {key} at stage {stage} checksum "
                        f"{prog[i].tolist()} != reference {want.tolist()}")
        out["plane_mismatch"] = {"value": bad, "limit": limits["plane_mismatch"],
                                 "stages": [s for s, _ in checksums]}

    rids = sample(cfg, mix, seed, served, admit, stage_log, prompts)
    gap_fn = reference.make_gap_fn(cfg, with_control=control)
    last = len(cfg["plane_widths"])
    groups = {g: {"program": 0.0, "control": 0.0, "tokens": 0, "requests": 0, "stages": set()}
              for g in ("partial", "full")}
    with jax.default_matmul_precision("highest"):
        for rid in rids:
            seq, tgt = reference.served_positions(prompts[rid], served[rid], mix["max_len"])
            st = reference.position_stages(len(prompts[rid]), admit[rid], stage_log[rid],
                                           mix["max_len"])
            stages, stage_of = np.unique(st, return_inverse=True)
            ms = np.array([stage_bits(cfg, int(s)) for s in stages], np.int32)
            res = [np.asarray(a) for a in gap_fn(raw, lohi, ms, stage_of.astype(np.int32),
                                                 seq, tgt)]
            for g, at in (("partial", st < last), ("full", st == last)):
                sel = at & (tgt >= 0)
                if not sel.any():
                    continue
                grp = groups[g]
                grp["program"] = max(grp["program"], float(res[0][sel].max()))
                if control:
                    grp["control"] = max(grp["control"], float(res[1][sel].max()))
                grp["tokens"] += int(sel.sum())
                grp["requests"] += 1
                grp["stages"].update(int(s) for s in np.unique(st[sel]))
    across = sum(len(set(stage_log[r])) > 1 for r in rids)
    log(f"  compared {len(rids)} requests, {across} across an upgrade; " + "; ".join(
        f"{g}: {grp['tokens']} served tokens at stages {sorted(grp['stages'])}"
        for g, grp in groups.items()))
    for g, grp in groups.items():
        if not grp["tokens"]:
            continue
        gap = {"value": grp["program"], "limit": limits[f"logit_gap_{g}"],
               "stages": sorted(grp["stages"]), "requests": grp["requests"],
               "tokens": grp["tokens"]}
        if control:
            out[f"program_logit_gap_{g}"] = {"value": grp["program"]}
            gap["value"], gap["control"] = grp["control"], "fp8"
        out[f"logit_gap_{g}"] = gap
    n_tok = sum(grp["tokens"] for grp in groups.values())
    out["uncompared"] = {"value": int(n_tok == 0), "limit": 0}
    return out
