"""The telemetry stack: registry, percentiles, exporters, tracer,
event schema, and the ``repro-telemetry`` analyzer.

Pins the ISSUE-10 acceptance surface:

* exact percentiles agree with ``np.percentile`` oracles (including
  random samples, extreme q, and tiny inputs);
* the registry interns by name, rejects kind collisions, and hands the
  shared no-op metric out while disabled;
* the Prometheus export round-trips through :func:`parse_prometheus`
  with values intact, and malformed text raises;
* every event a real ``browser-3g`` and ``browser-3g-lossy`` session
  emits validates against the schema registry — renames and payload
  drift fail loudly;
* ``repro-telemetry`` renders per-stage / latency / stall tables with
  p50/p99 from a SessionResult JSONL alone.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_config
from repro.core import wire
from repro.core.progressive import divide
from repro.models.model import build_model
from repro.obs import report as report_mod
from repro.obs.exporters import (parse_prometheus, to_jsonl, to_prometheus,
                                 to_summary)
from repro.obs.registry import (NULL_METRIC, Histogram, MetricsRegistry,
                                percentile)
from repro.obs.schema import (EVENT_SCHEMAS, SchemaError, validate_event,
                              validate_jsonl)
from repro.obs.tracer import Tracer
from repro.transmission import Session, get_scenario
from repro.transmission.session import FaultPolicy, SessionEvent


@pytest.fixture(scope="module")
def served():
    cfg = get_config("olmo-1b").reduced(n_layers=2, d_model=64, d_ff=128,
                                        vocab=128, n_heads=2, n_kv=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prog = divide(params)
    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (1, 8), 0, cfg.vocab).astype(jnp.int32)}
    return cfg, model, prog, batch


# ---------------------------------------------------------------------------
# percentiles: pinned against numpy
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("q", [0.0, 1.0, 25.0, 50.0, 75.0, 90.0, 99.0, 100.0])
@pytest.mark.parametrize("n", [1, 2, 3, 17, 100])
def test_percentile_matches_numpy_oracle(q, n):
    rng = np.random.default_rng(n * 1000 + int(q))
    vals = rng.normal(size=n).tolist()
    assert percentile(vals, q) == pytest.approx(
        float(np.percentile(vals, q)), rel=1e-12, abs=1e-12)


def test_percentile_random_q_sweep():
    rng = np.random.default_rng(7)
    vals = (rng.uniform(-1e3, 1e3, size=257)).tolist()
    for q in rng.uniform(0, 100, size=50):
        assert percentile(vals, float(q)) == pytest.approx(
            float(np.percentile(vals, q)), rel=1e-9)


def test_percentile_edge_cases():
    import math
    assert math.isnan(percentile([], 50.0))
    assert percentile([4.0], 0.0) == 4.0 == percentile([4.0], 100.0)
    with pytest.raises(ValueError, match=r"\[0, 100\]"):
        percentile([1.0], 101.0)
    with pytest.raises(ValueError, match=r"\[0, 100\]"):
        percentile([1.0], -0.5)


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

def test_registry_interning_labels_and_stats():
    reg = MetricsRegistry(enabled=True)
    c = reg.counter("reqs_total", "requests")
    assert reg.counter("reqs_total") is c          # interned by name
    c.inc(); c.inc(2, route="a"); c.inc(route="a")
    assert c.value() == 1.0
    assert c.value(route="a") == 3.0
    with pytest.raises(ValueError, match="cannot decrease"):
        c.inc(-1)

    g = reg.gauge("depth")
    g.set(5); g.inc(2); g.dec(3)
    assert g.value() == 4.0

    h = reg.histogram("lat_s")
    for v in (0.1, 0.2, 0.3, 0.4):
        h.observe(v, path="x")
    st = h.stats(quantiles=(50, 99), path="x")
    assert st["count"] == 4 and st["sum"] == pytest.approx(1.0)
    assert st["min"] == 0.1 and st["max"] == 0.4
    assert st["p50"] == pytest.approx(np.percentile([0.1, 0.2, 0.3, 0.4], 50))
    assert [m.name for m in reg.collect()] == ["depth", "lat_s", "reqs_total"]


def test_registry_kind_collision_raises():
    reg = MetricsRegistry(enabled=True)
    reg.counter("x_total")
    with pytest.raises(TypeError, match="already registered as counter"):
        reg.histogram("x_total")


def test_disabled_registry_is_inert():
    reg = MetricsRegistry(enabled=False)
    m = reg.counter("never_total")
    assert m is NULL_METRIC is reg.histogram("also_never")
    m.inc(5, any_label="v")        # all no-ops, nothing registered
    assert len(reg) == 0 and reg.collect() == []
    assert NULL_METRIC.value() == 0.0 and NULL_METRIC.samples() == []


# ---------------------------------------------------------------------------
# exporters: Prometheus round-trip + summary/jsonl views
# ---------------------------------------------------------------------------

def _populated_registry():
    reg = MetricsRegistry(enabled=True)
    reg.counter("bytes_total", "wire bytes").inc(1234, stage="1")
    reg.counter("bytes_total").inc(766, stage="2")
    reg.gauge("resident_bytes", "store residency").set(4096)
    h = reg.histogram("ttft_s", "time to first token")
    for v in (0.5, 1.0, 1.5, 2.0):
        h.observe(v, engine="pool")
    return reg


def test_prometheus_round_trip():
    reg = _populated_registry()
    text = to_prometheus(reg)
    fams = parse_prometheus(text)
    assert fams["bytes_total"]["type"] == "counter"
    assert fams["bytes_total"]["samples"]['bytes_total{stage="1"}'] == 1234.0
    assert fams["resident_bytes"]["type"] == "gauge"
    assert fams["resident_bytes"]["samples"]["resident_bytes"] == 4096.0
    # histograms export as summaries with exact quantiles + sum/count
    s = fams["ttft_s"]["samples"]
    assert fams["ttft_s"]["type"] == "summary"
    assert s['ttft_s{engine="pool",quantile="0.5"}'] == pytest.approx(
        float(np.percentile([0.5, 1.0, 1.5, 2.0], 50)))
    assert s['ttft_s_sum{engine="pool"}'] == pytest.approx(5.0)
    assert s['ttft_s_count{engine="pool"}'] == 4.0


@pytest.mark.parametrize("bad, match", [
    ("orphan_metric 1.0\n", "before its TYPE"),
    ("# TYPE x widget\nx 1\n", "unknown TYPE"),
    ("# TYPE x counter\nx notafloat\n", "bad value"),
    ("# HELP y only help\ny 2\n", "no TYPE line"),
], ids=["no-type", "bad-kind", "bad-float", "help-only"])
def test_parse_prometheus_rejects_malformed(bad, match):
    with pytest.raises(ValueError, match=match):
        parse_prometheus(bad)


def test_summary_and_jsonl_views():
    reg = _populated_registry()
    tracer = Tracer(reg)
    tracer.record("upgrade", wall_s=0.01, stage=3)
    summ = to_summary(reg, tracer)
    assert summ["counters"]["bytes_total"] == {"stage=1": 1234.0,
                                               "stage=2": 766.0}
    assert summ["gauges"]["resident_bytes"]["_"] == 4096.0
    hs = summ["histograms"]["ttft_s"]["engine=pool"]
    assert hs["count"] == 4 and "p99" in hs
    assert summ["spans"][0]["name"] == "upgrade"
    lines = to_jsonl(reg).strip().splitlines()
    recs = [json.loads(l) for l in lines]
    assert {r["metric"] for r in recs} == {"bytes_total", "resident_bytes",
                                           "ttft_s", "span_upgrade_wall_s"}
    assert all(r["type"] in ("counter", "gauge", "histogram") for r in recs)


# ---------------------------------------------------------------------------
# tracer: dual clocks
# ---------------------------------------------------------------------------

def test_tracer_dual_clock_records():
    reg = MetricsRegistry(enabled=True)
    tr = Tracer(reg)
    wall_only = tr.record("decode_window", wall_s=0.02, engine="pool")
    sim_only = tr.record("stage_arrival", sim_t0=0.0, sim_t1=3.5, stage=2)
    both = tr.record("upgrade_refresh", wall_s=0.001, sim_t0=1.0, sim_t1=1.25)
    assert wall_only.sim_s is None and "wall_s" in wall_only.to_dict()
    assert sim_only.wall_s is None and sim_only.sim_s == pytest.approx(3.5)
    assert both.to_dict()["sim_s"] == pytest.approx(0.25)
    # spans feed per-clock histograms
    assert isinstance(reg.get("span_decode_window_wall_s"), Histogram)
    assert reg.get("span_stage_arrival_sim_s").count(stage=2) == 1
    assert reg.get("span_stage_arrival_wall_s") is None
    assert tr.of("decode_window") == [wall_only]


def test_tracer_inert_when_disabled():
    reg = MetricsRegistry(enabled=False)
    tr = Tracer(reg)
    assert tr.record("x", wall_s=1.0) is None
    with tr.span("y"):
        pass
    assert tr.spans == [] and len(reg) == 0


def test_tracer_disabled_opens_no_annotation_and_reads_no_clock(monkeypatch):
    import time

    import jax.profiler

    def boom(*a, **k):
        raise AssertionError("disabled tracer touched the clock or profiler")

    monkeypatch.setattr(time, "perf_counter", boom)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    tr = Tracer(MetricsRegistry(enabled=False))
    with tr.span("engine_step", rows=3) as sp:
        assert sp is None
    assert tr.spans == []


def test_tracer_span_lands_on_profiler_host_plane(tmp_path):
    """An enabled span is a ``repro:<name>`` annotation on the host
    plane of a profiler trace, with its labels (including one added
    inside the block) as stats, on the line of the thread that ran it."""
    import threading

    from jax.profiler import ProfileData

    tr = Tracer(MetricsRegistry(enabled=True))

    def work(stage):
        with tr.span("client_decode", stage=stage) as sp:
            jnp.ones((8, 8)).sum().block_until_ready()
            sp["planes"] = 10 + stage

    jax.profiler.start_trace(str(tmp_path))
    try:
        th = threading.Thread(target=work, args=(2,))
        th.start()
        work(1)
        th.join(timeout=60)
    finally:
        jax.profiler.stop_trace()
    assert not th.is_alive()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    found = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name == "repro:client_decode":
                    st = dict(e.stats)
                    found[st["stage"]] = (i, st["planes"], e.duration_ns)
    assert set(found) == {1, 2}
    assert found[1][1] == 11 and found[2][1] == 12
    assert found[1][0] != found[2][0]           # one line per thread
    assert all(d > 0 for _, _, d in found.values())
    recs = tr.of("client_decode")
    assert sorted(r.labels["planes"] for r in recs) == [11, 12]
    assert all(r.wall_s > 0 for r in recs)


def test_registry_and_tracer_lose_no_update_across_threads():
    import sys
    import threading

    reg = MetricsRegistry(enabled=True)
    tr = Tracer(reg)
    n_threads, n = 16, 400

    def work():
        for _ in range(n):
            reg.counter("hits_total").inc()
            reg.gauge("level").inc()
            tr.record("tick", wall_s=0.0)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        ths = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in ths)
    total = n_threads * n
    assert reg.counter("hits_total").value() == total
    assert reg.gauge("level").value() == total
    assert len(tr.of("tick")) == total
    assert reg.get("span_tick_wall_s").count() == total


def test_global_telemetry_context_restores_and_clears():
    assert not obs.enabled()          # default-off is the contract
    with obs.telemetry(True) as reg:
        assert obs.enabled()
        reg.counter("scratch_total").inc()
        assert len(reg) == 1
    assert not obs.enabled()
    assert obs.get_registry().get("scratch_total") is None  # cleared


# ---------------------------------------------------------------------------
# event schema: replay real sessions
# ---------------------------------------------------------------------------

def test_schema_replay_browser_3g(served):
    """Every event of a clean browser-3g serving run validates; the
    JSONL export validates line by line."""
    cfg, model, prog, batch = served
    blob = wire.encode(prog)
    session = Session.from_scenario(blob, get_scenario("browser-3g"), seed=3)
    res = session.run_serving(model, prog, decode_steps=6, batch=batch)
    assert len(res.events) > 0
    for e in res.events:
        validate_event(e)
    assert validate_jsonl(res.to_jsonl()) == len(res.events)
    kinds = {e.kind for e in res.events}
    assert {"chunk", "stage_complete", "cold_start", "decode_step"} <= kinds


def test_schema_replay_browser_3g_lossy(served):
    """The fault-channel kinds (fault/quarantine/nack/repair/reconnect/
    transport_summary) validate too, on a real lossy run over the v3
    integrity wire."""
    cfg, model, prog, batch = served
    blob = wire.encode(prog, integrity=True)
    scenario = get_scenario("browser-3g-lossy")
    assert scenario.lossy
    # the reduced blob is only a handful of catalog-sized chunks, too
    # few draws for the ~1% channel to fire; shrink the chunk grid so
    # the lossy path deterministically exercises its event kinds
    session = Session.from_scenario(blob, scenario, seed=3, chunk_bytes=512)
    res = session.run_serving(model, prog, decode_steps=6, batch=batch,
                              faults=scenario.make_faults(3),
                              fault_policy=FaultPolicy(seed=1))
    for e in res.events:
        validate_event(e)
    assert validate_jsonl(res.to_jsonl()) == len(res.events)
    kinds = {e.kind for e in res.events}
    assert "transport_summary" in kinds
    assert kinds & {"fault", "quarantine", "nack", "repair", "reconnect"}


def test_schema_rejects_drift():
    with pytest.raises(SchemaError, match="unknown event kind"):
        validate_event(SessionEvent(0.0, "not_a_kind", {}))
    with pytest.raises(SchemaError, match="missing required"):
        validate_event(SessionEvent(0.0, "chunk", {"bytes": 10}))
    with pytest.raises(SchemaError, match="unexpected field"):
        validate_event(SessionEvent(0.0, "header", {"bytes": 1, "oops": 2}))
    with pytest.raises(SchemaError, match="got bool"):
        validate_event(SessionEvent(0.0, "chunk",
                                    {"bytes": True, "through": 1}))
    with pytest.raises(SchemaError, match="got str"):
        validate_event(SessionEvent(0.0, "repair",
                                    {"unit": 1, "attempt": 0, "ok": "yes"}))
    # JSONL records validate through the same path (envelope handling)
    with pytest.raises(SchemaError, match="envelope"):
        validate_jsonl('{"kind": "chunk", "bytes": 1, "through": 1}\n')
    assert "fault" in EVENT_SCHEMAS and EVENT_SCHEMAS["fault"].allow_extra


# ---------------------------------------------------------------------------
# repro-telemetry: the analyzer CLI
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def session_log(served, tmp_path_factory):
    cfg, model, prog, batch = served
    blob = wire.encode(prog)
    session = Session.from_scenario(blob, get_scenario("browser-3g"), seed=0)
    res = session.run_serving(model, prog, decode_steps=8, batch=batch)
    p = tmp_path_factory.mktemp("logs") / "browser3g.jsonl"
    p.write_text(res.to_jsonl())
    return p, res


def test_analyze_computes_stage_and_latency_tables(session_log):
    p, res = session_log
    rep = report_mod.analyze(report_mod.load_events(p))
    assert rep["events"] == len(res.events)
    stages = [r["stage"] for r in rep["stages"]]
    assert stages == sorted(stages) and stages[0] == 1
    for row in rep["stages"]:
        assert row["bytes"] > 0 and row["goodput_bps"] > 0
    assert rep["latency"]["ttft_s"] >= 0.0
    assert rep["latency"]["decode_gap_s"]["count"] >= 1
    assert "p50" in rep["stalls"]["chunk_gap_s"]
    assert "p99" in rep["stalls"]["chunk_gap_s"]


def test_analyze_accuracy_per_byte_column(session_log):
    p, _ = session_log
    events = report_mod.load_events(p)
    acc = {r["stage"]: 0.1 * r["stage"]
           for r in report_mod.analyze(events)["stages"]}
    rep = report_mod.analyze(events, accuracy=acc)
    for row in rep["stages"]:
        assert row["accuracy"] == pytest.approx(0.1 * row["stage"])
        assert row["acc_per_mb"] == pytest.approx(
            row["accuracy"] / (row["bytes"] / 2**20))


def test_report_cli_renders_tables(session_log, capsys):
    p, _ = session_log
    assert report_mod.main([str(p), "--validate"]) == 0
    out = capsys.readouterr().out
    assert "per-stage arrivals:" in out
    assert "ttft_s=" in out
    assert "p50" in out and "p99" in out


def test_report_cli_json_mode(session_log, capsys):
    p, _ = session_log
    assert report_mod.main([str(p), "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert str(p) in rep and rep[str(p)]["stages"]


def test_report_cli_check_prom(tmp_path, capsys):
    prom = tmp_path / "serve.prom"
    prom.write_text(to_prometheus(_populated_registry()))
    assert report_mod.main(["--check-prom", str(prom)]) == 0
    assert "OK" in capsys.readouterr().out
    bad = tmp_path / "bad.prom"
    bad.write_text("definitely not prometheus{ 1\n")
    with pytest.raises(ValueError):
        report_mod.main(["--check-prom", str(bad)])


def test_report_cli_requires_input(capsys):
    with pytest.raises(SystemExit):
        report_mod.main([])
