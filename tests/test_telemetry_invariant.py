"""The telemetry no-perturbation invariant (ISSUE-10 hard constraint).

With the registry disabled (the default) the instrumented hot paths
must behave *identically* to a process where :mod:`repro.obs` never
existed; with it enabled, observation must not move the byte clock or
the token stream. Both directions are pinned here by running the same
session twice — once inside ``obs.telemetry(False)``, once inside
``obs.telemetry(True)`` — and diffing the byte-exact JSONL event log
and the emitted tokens, across every engine shape: single-stream,
slot pool, speculative, and the faulted v3 transport.

Also pins the PR's satellite: every event carries a monotonic ``seq``,
the log sorts stably by ``(t_s, seq)``, and ``to_jsonl`` is
byte-deterministic across repeat runs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_config
from repro.core import wire
from repro.core.progressive import divide
from repro.models.model import build_model
from repro.serving.speculative import SpecConfig
from repro.transmission import BandwidthTrace, Session, get_scenario
from repro.transmission.session import FaultPolicy
from repro.transmission.simulator import FaultTrace


@pytest.fixture(scope="module")
def served():
    cfg = get_config("olmo-1b").reduced(n_layers=2, d_model=64, d_ff=128,
                                        vocab=128, n_heads=2, n_kv=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prog = divide(params)
    blob = wire.encode(prog)
    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab).astype(jnp.int32)}
    return cfg, model, prog, blob, batch


@pytest.fixture(autouse=True)
def _telemetry_off_between_tests():
    yield
    obs.configure(False)
    obs.reset()


def _diff_runs(go):
    """Run ``go`` with telemetry off and on; return both results after
    asserting the event logs are byte-identical."""
    with obs.telemetry(False):
        off = go()
    with obs.telemetry(True):
        on = go()
        assert len(obs.get_registry()) > 0, \
            "enabled run recorded nothing — instrumentation went dead"
    assert off.to_jsonl() == on.to_jsonl()
    return off, on


def test_single_stream_invariant(served):
    cfg, model, prog, blob, batch = served

    def go():
        session = Session.from_scenario(blob, get_scenario("browser-3g"),
                                        seed=3)
        return session.run_serving(model, prog, decode_steps=6, batch=batch)

    off, on = _diff_runs(go)
    np.testing.assert_array_equal(np.asarray(off.tokens),
                                  np.asarray(on.tokens))
    assert off.upgrades == on.upgrades
    assert off.stage_at_step == on.stage_at_step


def test_pool_invariant(served):
    cfg, model, prog, blob, batch = served
    prompts = [jax.random.randint(jax.random.PRNGKey(20 + i), (6,), 0,
                                  cfg.vocab).astype(jnp.int32)
               for i in range(3)]

    def go():
        session = Session(blob, BandwidthTrace.constant(100e3),
                          chunk_bytes=4096)
        return session.run_serving_pool(
            model, prog, prompts=prompts, max_new_tokens=4, n_slots=2,
            dispatch_window=2)

    off, on = _diff_runs(go)
    assert off.tokens == on.tokens
    assert off.admissions == on.admissions


def test_speculative_invariant(served):
    cfg, model, prog, blob, batch = served

    def go():
        session = Session.from_scenario(blob, get_scenario("browser-3g"),
                                        seed=0)
        return session.run_serving(model, prog, decode_steps=6, batch=batch,
                                   speculative=SpecConfig(draft_bits=4, k=2))

    off, on = _diff_runs(go)
    np.testing.assert_array_equal(np.asarray(off.tokens),
                                  np.asarray(on.tokens))
    assert off.speculation_summary() == on.speculation_summary()


def test_faulted_transport_invariant(served):
    """The fault path is the most byte-clock-sensitive code in the
    repo (every backoff float lands in the log): observing it must not
    move a single one."""
    cfg, model, prog, blob, batch = served
    blob3 = wire.encode(prog, integrity=True)
    faults = FaultTrace(seed=8, p_corrupt=0.06, p_truncate=0.04,
                        p_duplicate=0.04, p_disconnect=0.04)

    def go():
        session = Session(blob3, BandwidthTrace.constant(1e6),
                          chunk_bytes=1024, latency_s=0.01)
        return session.run_serving(model, prog, decode_steps=6, batch=batch,
                                   faults=faults,
                                   fault_policy=FaultPolicy(seed=1))

    off, on = _diff_runs(go)
    np.testing.assert_array_equal(np.asarray(off.tokens),
                                  np.asarray(on.tokens))
    assert off.transport == on.transport


def test_enabled_run_mirrors_log_into_registry(served):
    """One source of truth: the counters are thin views over the event
    log, so their totals must equal what the log says."""
    cfg, model, prog, blob, batch = served
    with obs.telemetry(True):
        session = Session.from_scenario(blob, get_scenario("browser-3g"),
                                        seed=3)
        res = session.run_serving(model, prog, decode_steps=6, batch=batch)
        reg = obs.get_registry()
        assert reg.get("session_chunks_total").value() == \
            len(res.events_of("chunk"))
        assert reg.get("session_bytes_total").value() == \
            sum(e.data["bytes"] for e in res.events_of("chunk"))
        n_stages = sum(
            reg.get("session_stage_completions_total").value(stage=s)
            for s in range(1, prog.n_stages + 1))
        assert n_stages == len(res.events_of("stage_complete"))
        # one store ingest per completed stage, timed where it happens
        ingests = obs.get_tracer().of("store_ingest")
        assert len(ingests) == len(res.events_of("stage_complete"))
        assert all(s.wall_s is not None for s in ingests)
        # dual-clock spans: stage arrivals live on the sim clock
        arrivals = obs.get_tracer().of("stage_arrival")
        assert len(arrivals) == len(res.events_of("stage_complete"))
        assert all(s.sim_s is not None and s.wall_s is None
                   for s in arrivals)
        # engine decode windows live on the wall clock
        windows = obs.get_tracer().of("decode_window")
        assert windows and all(s.wall_s is not None for s in windows)
        # the slot pool times each step it dispatches
        pool = Session(blob, BandwidthTrace.constant(100e3),
                       chunk_bytes=4096).run_serving_pool(
            model, prog, prompts=[batch["tokens"][0]], max_new_tokens=3,
            n_slots=2, dispatch_window=2)
        steps = obs.get_tracer().of("engine_step")
        assert steps and all(s.wall_s is not None for s in steps)
        assert reg.get("span_engine_step_wall_s").count() == len(steps)
        assert pool.tokens


def test_seq_is_monotonic_and_serialized(served):
    cfg, model, prog, blob, batch = served
    session = Session.from_scenario(blob, get_scenario("edge-stall"), seed=0)
    res = session.run_serving(model, prog, decode_steps=6, batch=batch)
    seqs = [e.seq for e in res.events]
    assert len(set(seqs)) == len(seqs)              # unique
    ts = [(e.t_s, e.seq) for e in res.events]
    assert ts == sorted(ts)                          # stable (t_s, seq) order
    # equal-timestamp neighbours keep emission order via seq
    import json as _json
    for line in res.to_jsonl().strip().splitlines():
        assert "seq" in _json.loads(line)


def test_jsonl_byte_deterministic_across_runs(served):
    cfg, model, prog, blob, batch = served

    def go():
        session = Session.from_scenario(blob, get_scenario("browser-3g"),
                                        seed=5)
        return session.run_serving(model, prog, decode_steps=6,
                                   batch=batch).to_jsonl()

    assert go() == go()
