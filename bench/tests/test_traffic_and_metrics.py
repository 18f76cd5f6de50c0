"""The traffic generator and the end-to-end metric arithmetic, on the CPU."""
import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import harness, traffic

MIXES = Path(__file__).resolve().parents[1] / "traffic"


def mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["chat", "chat_coldstart"])
def test_seed_determinism_and_clips(name):
    m = mix(name)
    a = traffic.generate(m, 2**31 + 17, 45, 50304)
    b = traffic.generate(m, 2**31 + 17, 45, 50304)
    c = traffic.generate(m, 5, 45, 50304)
    assert [(r.due_s, r.out_len, r.prompt.tolist()) for r in a] == \
           [(r.due_s, r.out_len, r.prompt.tolist()) for r in b]
    for reqs in (a, c):
        p = np.array([len(r.prompt) for r in reqs])
        o = np.array([r.out_len for r in reqs])
        assert p.min() >= m["prompt_tokens"]["min"] and p.max() <= m["prompt_tokens"]["max"]
        assert o.min() >= m["output_tokens"]["min"] and o.max() <= m["output_tokens"]["max"]
        assert all(0 <= t < 50304 for r in reqs for t in r.prompt[:8])
    # every seed does the same work, in the same order; only token ids differ
    assert [(r.due_s, len(r.prompt), r.out_len) for r in a] == \
           [(r.due_s, len(r.prompt), r.out_len) for r in c]
    assert a[0].prompt.tolist() != c[0].prompt.tolist()


def test_poisson_arrivals():
    m = mix("chat_coldstart")
    reqs = traffic.generate(m, 3, 45, 100)
    k = m["arrivals"]["at_start"]
    due = np.array([r.due_s for r in reqs])
    assert len(reqs) == k + round(m["arrivals"]["rate_per_s"] * 45)
    assert (due[:k] == 0).all() and (np.diff(due) >= 0).all()
    # stratified gaps: the last arrival lands near the window's end
    assert 0.8 * 45 < due[-1] < 1.2 * 45
    median = np.median(np.array([len(r.prompt) for r in reqs]))
    assert abs(median - m["prompt_tokens"]["median"]) <= 0.1 * m["prompt_tokens"]["median"]


def test_link_rate_and_chunks():
    C = 1 << 20
    s = {"rate_bytes_per_s": 200e6, "chunk_bytes": C, "latency_s": 0.005}
    total = 10 * C + 5
    link = traffic.Link(s, total)
    assert link.available(0.004) == 0
    t1 = 0.005 + C / 200e6
    assert link.next_s() == pytest.approx(t1)
    assert link.available(t1 - 1e-9) == 0
    assert link.available(t1 + 1e-9) == C
    # a receiver that falls behind finds every whole chunk that has arrived
    assert link.available(0.005 + 4.5 * C / 200e6) == 4 * C
    link.take(4 * C)
    assert link.available(0.005 + 4.5 * C / 200e6) == 0
    assert link.next_s() == pytest.approx(0.005 + 5 * C / 200e6)
    link.take(6 * C)
    # the last chunk is short, and comes once the whole wire has arrived
    assert link.available(0.005 + (10 * C + 2) / 200e6) == 0
    assert link.available(1.0) == 5
    link.take(5)
    assert link.next_s() is None


def run_view(requests, upgrades=(), window=10.0, n_stages=8, span=None):
    rec = harness.Record(requests={i: r for i, r in enumerate(requests)},
                         upgrades=list(upgrades), window_s=window)
    return SimpleNamespace(rec=rec, n_stages=n_stages, span=span or (0.0, window),
                           setup_s=1.0, trace=None)


def req(due, tokens, prompt_len=10):
    return {"due": due, "tokens": list(tokens), "prompt_len": prompt_len, "finished": None}


def test_ttft_from_due_time_with_unfinished():
    read = harness.load_reader("ttft_p50_s")
    # four answered 1 s after they were due, three never
    rs = [req(i * 0.5, [i * 0.5 + 1.0]) for i in range(4)] + [req(6.0 + i, []) for i in range(3)]
    v = read(run_view(rs, window=10.0))
    waits = [1.0] * 4 + [4.0, 3.0, 2.0]
    assert v == pytest.approx(float(np.percentile(waits, 50)))
    # a request due after the window does not count; a token after it is none
    rs.append(req(11.0, [11.5]))
    rs.append(req(9.0, [10.5]))
    v2 = read(run_view(rs, window=10.0))
    assert v2 == pytest.approx(float(np.percentile(waits + [1.0], 50)))


def test_itl_from_flush_times():
    read = harness.load_reader("itl_p95_s")
    # one flush returns 4 tokens at once (gaps 0), the next 2 tokens 0.5 s later
    rs = [req(0.0, [1.0, 1.0, 1.0, 1.0, 1.5, 1.5]), req(0.0, [2.0, 3.0])]
    v = read(run_view(rs))
    gaps = [0, 0, 0, 0.5, 0, 1.0]
    assert v == pytest.approx(float(np.percentile(gaps, 95)))


def test_tokens_per_s_counts_window_only():
    read = harness.load_reader("tokens_per_s")
    rs = [req(0.0, [1, 2, 3, 11]), req(0.0, [9.5])]
    assert read(run_view(rs, window=10.0)) == pytest.approx(4 / 10.0)


def test_stage_s_all_landed_and_stalled():
    read = harness.load_reader("stage_s")
    ups = [(2.0 * k, k) for k in range(1, 9)]
    assert read(run_view([], ups, window=30.0)) == pytest.approx(16.0 / 8)
    # stalled after stage 3 at 6 s in a 30 s window: 30 / 4 beats 6 / 3
    assert read(run_view([], ups[:3], window=30.0)) == pytest.approx(30.0 / 4)
    # stage 3 lands just before the end: no jump against a stall
    assert read(run_view([], [(9.0, 1), (19.0, 2), (29.9, 3)], window=30.0)) \
        == pytest.approx(max(29.9 / 3, 30.0 / 4))
    # no stage at all: the whole window
    assert read(run_view([], [], window=30.0)) == pytest.approx(30.0)
    # a stage skipped (1 -> 3) counts at the stage reached
    assert read(run_view([], [(4.0, 1), (9.0, 3)], window=30.0)) == pytest.approx(30.0 / 4)
