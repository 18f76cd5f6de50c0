"""Self-speculative progressive decoding: the ISSUE-5 acceptance
surface.

1. Truncated views: ``PlaneStore.quantized_leaves(bits=b)`` /
   ``QuantizedTensor.truncate(b)`` are bit-identical to freshly
   quantizing at b bits (every container dtype, sliced expert banks),
   share the accumulator buffer verbatim, and add zero resident bytes.
2. KV rollback: random accept/reject patterns across speculation
   rounds leave the *attended region* of every cache byte-identical to
   a plain sequential decode of the accepted tokens — full caches and
   wrapped ring caches, ragged per-slot positions included. Rejected
   rows are never copied away, only overwritten.
3. Losslessness: speculative decode emits exactly the plain greedy
   stream at every precision stage, single-stream and slot-pool, with
   exactly two decode executables (draft decode_step + target
   verify_step) and zero recompiles across mid-speculation upgrades.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.bitplanes import PlaneSchedule
from repro.core.plane_store import PlaneStore
from repro.core.policy import (ExpertPopularityPolicy, SpeculationController,
                               UniformPolicy)
from repro.core.progressive import divide
from repro.core.quantize import QuantizedTensor, dequantize, quantize
from repro.models.common import masked_q
from repro.models.model import build_model
from repro.serving.engine import PoolRequest, ProgressiveServer
from repro.serving.speculative import (SpecConfig, SpeculativeEngine,
                                       SpeculativeSlotPool)

SCHEDULES = {
    "uint8": PlaneSchedule(bits=8, widths=(2, 2, 2, 2)),
    "uint16": PlaneSchedule(bits=16, widths=(4, 4, 4, 4)),
    "uint32": PlaneSchedule(bits=20, widths=(5, 5, 5, 5)),
}


def _tiny(arch="olmo-1b", **over):
    base = dict(n_layers=2, d_model=64, d_ff=128, vocab=128,
                n_heads=2, n_kv=2)
    base.update(over)
    cfg = get_config(arch).reduced(**base)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _stage_replay(model, prog, prompt, stage_log, admit_stage=1):
    """Plain greedy tokens replayed at a speculative run's per-token
    stage schedule. Convention: token j's VALUE is computed at
    stage_log[j] and its K/V is written by the step that computes token
    j+1 — i.e. at stage_log[j+1]. That is exactly the speculative
    timing: accepted drafts are fed (K/V written) by the round that
    emits them, and a round's correction token is fed by the NEXT
    round, after any upgrade landing at the boundary."""
    srv = ProgressiveServer(model, prog,
                            max_len=prompt.shape[-1] + len(stage_log),
                            resident="quantized")
    for _ in range(admit_stage):
        srv.receive_stage()
    prompt2 = prompt if prompt.ndim == 2 else prompt[None]
    srv.start({"tokens": prompt2})
    assert stage_log[0] == admit_stage
    out = [int(np.asarray(jnp.argmax(srv.last_logits, axis=-1))[0])]
    caches = srv.caches
    pos = int(prompt2.shape[1])
    for stg in stage_log[1:]:
        while srv.stage < stg:
            srv.receive_stage()
        logits, caches = srv._decode(
            srv.params, caches, jnp.asarray([[out[-1]]], jnp.int32),
            jnp.int32(pos))
        pos += 1
        out.append(int(np.asarray(jnp.argmax(logits, axis=-1))[0]))
    return out


# ---------------------------------------------------------------------------
# satellite: truncated-view parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("container", sorted(SCHEDULES))
def test_truncated_view_parity(container):
    """quantized_leaves(bits=b) on a store holding MORE than b bits is
    bit-identical to freshly quantizing the source at b bits — both at
    the q level (floor-quantization prefix property, after shifting out
    the masked low planes) and at the dequantized-value level — and
    shares the full view's accumulator buffer verbatim."""
    sched = SCHEDULES[container]
    w = {"wq": jax.random.normal(jax.random.PRNGKey(1), (24, 40)) * 2.0}
    prog = divide(w, UniformPolicy(schedule=sched))
    store = PlaneStore.from_model(prog)
    for s in range(1, prog.n_stages + 1):
        store.ingest(prog.stage(s))
    full = store.quantized_leaves()
    key = prog.tensors[0].path
    for b in sched.cumulative_bits[:-1]:
        leaf = store.quantized_leaves(bits=b)[key]
        assert leaf.q is full[key].q, "truncated view must share q"
        fresh = quantize(w["wq"], b)
        mq = masked_q(leaf)
        np.testing.assert_array_equal(
            np.asarray((mq >> (sched.bits - b)).astype(fresh.q.dtype)),
            np.asarray(fresh.q), err_msg=f"{container} b={b}")
        got = mq.astype(jnp.float32) * leaf.scale + leaf.offset
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(dequantize(fresh)),
                                      err_msg=f"{container} b={b} dequant")
        assert int(np.asarray(leaf.keep_bits).ravel()[0]) == b


def test_truncated_view_sliced_expert_bank():
    """Sliced banks truncate per slice: each expert keeps its own
    (lo, hi) range, so the b-bit view must equal per-expert fresh
    quantization at b bits."""
    E, d, f = 3, 8, 16
    w = jax.random.normal(jax.random.PRNGKey(3), (E, d, f)) \
        * jnp.arange(1, E + 1, dtype=jnp.float32)[:, None, None]
    prog = divide({"we_gate": w},
                  ExpertPopularityPolicy(schedule=SCHEDULES["uint8"],
                                         n_experts=E))
    store = PlaneStore.from_model(prog)
    for s in range(1, prog.n_stages + 1):
        store.ingest(prog.stage(s))
    b = 4
    leaf = store.quantized_leaves(bits=b)[prog.tensors[0].path]
    assert leaf.q is store.quantized_leaves()[prog.tensors[0].path].q
    got = masked_q(leaf).astype(jnp.float32) * leaf.scale + leaf.offset
    for e in range(E):
        want = dequantize(quantize(w[e], b))
        np.testing.assert_array_equal(np.asarray(got[e]), np.asarray(want),
                                      err_msg=f"expert {e}")
    assert np.asarray(leaf.keep_bits).ravel().tolist() == [b] * E


def test_truncate_beyond_received_is_full_view():
    """Asking for more bits than have arrived degrades gracefully to
    the received precision (the draft == target early-download case)."""
    sched = SCHEDULES["uint16"]
    w = {"wq": jax.random.normal(jax.random.PRNGKey(2), (16, 16))}
    prog = divide(w, UniformPolicy(schedule=sched))
    store = PlaneStore.from_model(prog)
    store.ingest(prog.stage(1))  # 4 of 16 bits received
    key = prog.tensors[0].path
    tr = store.quantized_leaves(bits=12)[key]
    full = store.quantized_leaves()[key]
    got = masked_q(tr).astype(jnp.float32) * tr.scale + tr.offset
    want = full.q.astype(jnp.float32) * full.scale + full.offset
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(np.asarray(tr.received_bits).ravel()[0]) == 4


def test_truncated_view_cache_invalidated_by_ingest():
    sched = SCHEDULES["uint8"]
    w = {"wq": jax.random.normal(jax.random.PRNGKey(4), (8, 8))}
    prog = divide(w, UniformPolicy(schedule=sched))
    store = PlaneStore.from_model(prog)
    store.ingest(prog.stage(1))
    key = prog.tensors[0].path
    a = store.quantized_leaves(bits=2)[key]
    assert store.quantized_leaves(bits=2)[key] is a  # cached
    store.ingest(prog.stage(2))
    b = store.quantized_leaves(bits=2)[key]
    assert b is not a  # ingest invalidates the truncated cache too


# ---------------------------------------------------------------------------
# KV rollback: verify blocks leave the attended cache region
# byte-identical to sequential decode (shared driver; the hypothesis
# sweep over random patterns lives in test_spec_rollback.py)
# ---------------------------------------------------------------------------

P_LEN = 4      # prompt tokens
STREAM = 14    # accepted tokens per slot
K_MAX = 4      # max draft length per round


def rollback_setup(kind: str):
    """One full-attention and one ring-cache model with jitted entry
    points (ring: window 6, wrapped twice over the 18-position run)."""
    arch, over = {
        "full": ("olmo-1b", dict(n_layers=2, d_model=32, d_ff=64,
                                 vocab=64, n_heads=2, n_kv=2)),
        "ring": ("mixtral-8x22b", dict(n_layers=2, d_model=32, d_ff=64,
                                       vocab=64, n_heads=2, n_kv=2,
                                       n_experts=2, top_k=1, window=6)),
    }[kind]
    cfg = get_config(arch).reduced(**over)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(5))
    return (cfg, model, params, jax.jit(model.prefill),
            jax.jit(model.decode_step), jax.jit(model.verify_step))


def _attended_region_equal(cfg, spec_caches, ref_caches, slot: int,
                           n: int) -> None:
    """Assert byte-identity of every KV leaf on the region a next query
    at position ``n`` could attend: full caches on indices [0, n); ring
    caches on the slots of claimed positions (n - window, n). Rejected
    draft rows live OUTSIDE this region by construction (the rollback
    invariant) and are intentionally not compared — they are dead bytes
    awaiting overwrite."""
    sl, _ = jax.tree_util.tree_flatten(spec_caches)
    rl, _ = jax.tree_util.tree_flatten(ref_caches)
    assert len(sl) == len(rl)
    W = cfg.window
    for a, r in zip(sl, rl):
        S = a.shape[-2]
        assert r.shape[-2] == S
        a_np = np.asarray(jnp.moveaxis(a, -2, 0))  # (S, ..., hd)
        r_np = np.asarray(jnp.moveaxis(r, -2, 0))
        # batch axis after the move: 1 (non-stacked) or 2 (stacked)
        bax = 2 if a.ndim == 5 else 1
        a_np = np.take(a_np, slot, axis=bax)
        r_np = np.take(r_np, 0, axis=bax)
        if W and S == W + K_MAX + 1:  # margin-grown ring
            idx = sorted({c % S for c in range(max(0, n - W + 1), n)})
        else:                          # full cache
            idx = list(range(min(n, S)))
        np.testing.assert_array_equal(
            a_np[idx], r_np[idx],
            err_msg=f"cache leaf {tuple(a.shape)} slot {slot} "
                    f"attended region")


def run_rollback_pattern(setup, prompts, streams, draw_k, draw_acc):
    """Drive batched verify blocks whose accepted prefixes follow the
    predetermined per-slot token streams, with the accept/reject
    pattern supplied by ``draw_k()`` / ``draw_acc(k, room)``; then
    assert every slot's attended cache region is byte-identical to a
    plain B=1 sequential decode of its accepted stream."""
    cfg, model, params, prefill, decode, verify = setup
    B, V = prompts.shape[0], cfg.vocab
    max_len = P_LEN + STREAM + K_MAX + 1
    _, caches = prefill(params, {"tokens": jnp.asarray(prompts)})
    caches = model.grow_caches(caches, max_len, ring_margin=K_MAX + 1,
                               pos=P_LEN)
    fed = [0] * B
    guard = 0
    while min(fed) < STREAM:
        guard += 1
        assert guard < 10 * STREAM
        k = draw_k()
        accs, base, block = [], [], []
        for b in range(B):
            if fed[b] >= STREAM:
                accs.append(0)
                base.append(-1)          # finished slot: masked rows
                block.append(np.zeros((k + 1,), np.int32))
                continue
            a = draw_acc(k, STREAM - 1 - fed[b])
            accs.append(a)
            base.append(P_LEN + fed[b])
            # wrapped continuation of the stream, then corrupt the
            # rejected tail so it provably differs from the real stream
            blk = np.resize(streams[b], (fed[b] + k + 1,))[fed[b]:].copy()
            blk[a + 1:] = (blk[a + 1:] + 1) % V
            block.append(blk.astype(np.int32))
        _, caches = verify(params, caches,
                           jnp.asarray(np.stack(block)),
                           jnp.asarray(base, dtype=jnp.int32))
        for b in range(B):
            if fed[b] < STREAM:
                fed[b] += accs[b] + 1
    for b in range(B):
        _, ref = prefill(params, {"tokens": jnp.asarray(prompts[b][None])})
        ref = model.grow_caches(ref, max_len, ring_margin=K_MAX + 1,
                                pos=P_LEN)
        for j in range(STREAM):
            _, ref = decode(params, ref,
                            jnp.asarray([[streams[b, j]]], jnp.int32),
                            jnp.asarray([P_LEN + j], jnp.int32))
        _attended_region_equal(cfg, caches, ref, b, P_LEN + STREAM)


@pytest.mark.parametrize("kind", ["full", "ring"])
@pytest.mark.parametrize("pattern", ["reject_all", "alternate", "accept_all"])
def test_kv_rollback_fixed_patterns(kind, pattern):
    """Deterministic accept/reject schedules, ragged across the two
    slots (slot 1 always accepts one fewer than slot 0): the attended
    cache region must match sequential decode byte for byte — including
    ring wraparound (window 6 over 18 positions)."""
    setup = rollback_setup(kind)
    rng = np.random.RandomState(0)
    prompts = rng.randint(0, setup[0].vocab, (2, P_LEN)).astype(np.int32)
    streams = rng.randint(0, setup[0].vocab, (2, STREAM)).astype(np.int32)
    state = {"flip": 0, "slot": 0}

    def draw_k():
        state["slot"] = 0
        return K_MAX

    def draw_acc(k, room):
        state["flip"] ^= 1
        state["slot"] += 1
        a = {"reject_all": 0, "alternate": k if state["flip"] else 0,
             "accept_all": k}[pattern]
        return min(max(a - (state["slot"] - 1), 0), room)

    run_rollback_pattern(setup, prompts, streams, draw_k, draw_acc)


# ---------------------------------------------------------------------------
# losslessness: token identity at every stage, both serving shapes
# ---------------------------------------------------------------------------

def test_single_stream_token_identity_all_stages():
    """One speculative engine across the whole ladder: at every stage
    the emitted stream equals plain greedy, with <= 2 decode
    executables over the ENTIRE run (1 while no precision gap exists,
    2 once drafting starts — zero recompiles per upgrade)."""
    cfg, model, params = _tiny()
    prog = divide(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                cfg.vocab).astype(jnp.int32)
    steps = 10
    spec = SpeculativeEngine(model, prog, max_len=8 + steps + 9,
                             spec=SpecConfig(draft_bits=4, k=4))
    plain = ProgressiveServer(model, prog, max_len=8 + steps + 9,
                              resident="quantized")
    for s in range(1, prog.n_stages + 1):
        spec.receive_stage()
        plain.receive_stage()
        spec.start({"tokens": tokens})
        plain.start({"tokens": tokens})
        got = np.asarray(spec.decode(steps).tokens)
        want = np.asarray(plain.decode(steps).tokens)
        np.testing.assert_array_equal(got, want, err_msg=f"stage {s}")
    assert spec.decode_cache_size() == 2


def test_ring_cache_token_identity_past_wraparound():
    cfg, model, params = _tiny("mixtral-8x22b", d_model=32, d_ff=64,
                               vocab=64, n_experts=2, top_k=1, window=8)
    prog = divide(params)
    prompt = jax.random.randint(jax.random.PRNGKey(7), (1, 9), 0,
                                cfg.vocab).astype(jnp.int32)
    steps = 14  # crosses the window-8 boundary
    plain = ProgressiveServer(model, prog, max_len=9 + steps + 9,
                              resident="quantized")
    spec = SpeculativeEngine(model, prog, max_len=9 + steps + 9,
                             spec=SpecConfig(draft_bits=6, k=4))
    for _ in range(prog.n_stages):
        plain.receive_stage()
        spec.receive_stage()
    plain.start({"tokens": prompt})
    spec.start({"tokens": prompt})
    np.testing.assert_array_equal(np.asarray(spec.decode(steps).tokens),
                                  np.asarray(plain.decode(steps).tokens))


def test_midstream_upgrades_match_stage_replay():
    """Upgrades landing between speculation rounds: the emitted stream
    must equal a plain server replayed at the SAME per-token stage
    schedule (the speculative analogue of the slot-pool replay test),
    and no upgrade may add an executable."""
    cfg, model, params = _tiny()
    prog = divide(params)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, 8), 0,
                                cfg.vocab).astype(jnp.int32)
    steps = 14
    spec = SpeculativeEngine(model, prog, max_len=8 + steps + 9,
                             spec=SpecConfig(draft_bits=4, k=3))
    spec.receive_stage()
    spec.start({"tokens": tokens})
    res = spec.decode(steps, stage_arrival=lambda i: True)
    # upgrades land at ROUND granularity (the speculative analogue of
    # the pool's window granularity), so several — not necessarily all —
    # stages arrive mid-generation
    assert len(res.upgrades) >= 2
    assert spec.stage == 1 + len(res.upgrades)
    assert spec.decode_cache_size() == 2

    got = np.asarray(res.tokens)[0].tolist()
    assert got == _stage_replay(model, prog, tokens, res.stage_log[0])


def test_pool_token_identity_and_ragged_budgets():
    """Speculative slot pool vs the plain single-stream server, per
    slot at the final stage — different prompt lengths, budgets met
    exactly, one draft + one verify executable."""
    cfg, model, params = _tiny()
    prog = divide(params)
    prompts = [jax.random.randint(jax.random.PRNGKey(10 + i), (L,), 0,
                                  cfg.vocab).astype(jnp.int32)
               for i, L in enumerate([4, 8, 6, 8])]
    steps = 8
    pool = SpeculativeSlotPool(model, prog, n_slots=3,
                               max_len=8 + steps + 10,
                               spec=SpecConfig(draft_bits=4, k=3),
                               dispatch_window=2)
    for _ in range(prog.n_stages):
        pool.receive_stage()
    for i, p in enumerate(prompts):
        pool.submit(PoolRequest(rid=i, prompt=p, max_new_tokens=steps))
    out = pool.run()
    assert pool.decode_cache_size() == 2
    assert pool.completed == {0, 1, 2, 3}
    for rid, p in enumerate(prompts):
        srv = ProgressiveServer(model, prog, max_len=8 + steps + 10,
                                resident="quantized")
        for _ in range(prog.n_stages):
            srv.receive_stage()
        srv.start({"tokens": p[None]})
        want = np.asarray(srv.decode(steps).tokens)[0].tolist()
        assert out[rid] == want, f"rid {rid}"
        assert len(out[rid]) == steps


def test_pool_midflight_upgrades_match_replay():
    """Precision stages landing between pool speculation rounds: each
    rid's stream equals the plain server replayed at its own per-token
    stage log."""
    cfg, model, params = _tiny()
    prog = divide(params)
    prompts = [jax.random.randint(jax.random.PRNGKey(20 + i), (6,), 0,
                                  cfg.vocab).astype(jnp.int32)
               for i in range(2)]
    steps = 10
    pool = SpeculativeSlotPool(model, prog, n_slots=2,
                               max_len=6 + steps + 10,
                               spec=SpecConfig(draft_bits=4, k=2),
                               dispatch_window=1)
    pool.receive_stage()
    for i, p in enumerate(prompts):
        pool.submit(PoolRequest(rid=i, prompt=p, max_new_tokens=steps))
    out = pool.run(on_window=lambda _: pool.upgrade_if_available())
    # window-granularity upgrades: several stages land mid-flight
    # (round counts, not stage counts, bound how many)
    assert pool.stage > 2
    assert pool.decode_cache_size() == 2
    for rid, p in enumerate(prompts):
        want = _stage_replay(model, prog, p, pool.stage_log[rid],
                             admit_stage=pool.admit_stage[rid])
        assert out[rid] == want, f"rid {rid}"


# ---------------------------------------------------------------------------
# jaxpr regression: zero cache-sized copies per verify round
# ---------------------------------------------------------------------------

def _collect_eqns(jaxpr):
    """All eqns including nested (scan/cond/jit) bodies."""
    out = []
    stack = [jaxpr]
    while stack:
        j = stack.pop()
        for eqn in j.eqns:
            out.append(eqn)
            for v in eqn.params.values():
                vals = v if isinstance(v, (tuple, list)) else (v,)
                for item in vals:
                    if hasattr(item, "jaxpr"):
                        stack.append(item.jaxpr)
                    elif hasattr(item, "eqns"):
                        stack.append(item)
    return out


@pytest.mark.parametrize("kind", ["full", "ring"])
def test_verify_step_jaxpr_zero_cache_copies(kind):
    """Rollback is overwrite-only: tracing verify_step must show NO
    cache-sized transpose/copy/concatenate (a snapshot-and-restore
    rollback would), and each KV cache leaf is written by exactly the
    functional update(s) of its own block — every cache byte crosses
    once, rejected rows included."""
    cfg, model, params, _, _, _ = rollback_setup(kind)
    B, S, T, max_len = 2, P_LEN, K_MAX + 1, P_LEN + STREAM + K_MAX + 1
    _, caches = model.prefill(params, {"tokens": jnp.zeros((B, S), jnp.int32)})
    caches = model.grow_caches(caches, max_len, ring_margin=K_MAX + 1,
                               pos=S)
    jaxpr = jax.make_jaxpr(model.verify_step)(
        params, caches, jnp.zeros((B, T), jnp.int32),
        jnp.full((B,), S, jnp.int32))
    cache_sizes = set()
    for leaf in jax.tree.leaves(caches):
        if leaf.ndim >= 4:
            cache_sizes.add(int(np.prod(leaf.shape[-4:])))
    assert cache_sizes
    offenders, writes = [], 0
    for eqn in _collect_eqns(jaxpr.jaxpr):
        name = eqn.primitive.name
        # a COPY duplicates the cache: its *output* is cache-sized.
        # (Cache-sized inputs with small outputs — e.g. the masked
        # write's block-read — move O(T) bytes, not O(S).)
        sized_out = any(v.aval.ndim >= 4
                        and int(np.prod(v.aval.shape[-4:])) in cache_sizes
                        for v in eqn.outvars if hasattr(v.aval, "shape"))
        if not sized_out:
            continue
        if name in ("transpose", "copy", "concatenate", "gather"):
            offenders.append((name, [v.aval.shape for v in eqn.outvars]))
        if name in ("dynamic_update_slice", "scatter"):
            writes += 1
    assert not offenders, f"cache-sized copies in verify_step: {offenders}"
    # one traced attention block per cycle (scan traces the body once):
    # k + v writes, once per verify token on rings, once for the whole
    # block on full caches (into the stack the layer loop carries)
    per_block = 2 * T if kind == "ring" else 2
    assert writes == per_block, (writes, per_block)


# ---------------------------------------------------------------------------
# audits: zero extra bytes, effective_bits, recompiles
# ---------------------------------------------------------------------------

def test_zero_extra_resident_bytes_and_effective_bits():
    cfg, model, params = _tiny()
    prog = divide(params)
    spec = SpeculativeEngine(model, prog, max_len=24,
                             spec=SpecConfig(draft_bits=4, k=2))
    for _ in range(prog.n_stages):
        spec.receive_stage()
    rep = spec.resident_report()
    assert rep["extra_draft_bytes"] == 0
    assert rep["aliased_leaves"] > 0
    eff = set(rep["effective_bits"].values())
    # both views audited together: the 4-bit draft and the 16-bit
    # target are distinguishable per leaf even though buffers alias
    assert eff == {4, 16}
    # every draft q buffer IS the target q buffer
    td = jax.tree_util.tree_leaves(
        spec.params, is_leaf=lambda x: isinstance(x, QuantizedTensor))
    dd = jax.tree_util.tree_leaves(
        spec.draft_params, is_leaf=lambda x: isinstance(x, QuantizedTensor))
    for t, d in zip(td, dd):
        if isinstance(t, QuantizedTensor):
            assert d.q is t.q


def test_ssm_archs_rejected():
    """Recurrent state has no overwrite-only rollback; the engine must
    refuse such architectures at construction."""
    cfg = get_config("xlstm-125m").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prog = divide(params)
    with pytest.raises(NotImplementedError, match="rollback"):
        SpeculativeEngine(model, prog, max_len=24,
                          spec=SpecConfig(draft_bits=4, k=2))


# ---------------------------------------------------------------------------
# controller + session integration
# ---------------------------------------------------------------------------

def test_controller_ladder():
    c = SpeculationController(draft_bits=4, k_max=8, k_init=4)
    assert c.choose_k(received_bits=2) == 0    # no gap -> plain decode
    assert c.choose_k(received_bits=4) == 0
    assert c.choose_k(received_bits=16) == 4
    for _ in range(6):
        c.update(accepted=8, proposed=8)       # perfect acceptance
    assert c.choose_k(16) == 8                 # climbed to k_max
    for _ in range(14):
        c.update(accepted=0, proposed=8)       # everything rejected
    assert c.choose_k(16) == 1                 # floor of the ladder, not 0
    # rejection persisting AT the floor climbs the draft's precision
    # ladder instead (a finer prefix of the same accumulators)
    assert c.draft_bits == c.max_draft_bits == 8
    r = c.rate
    c.on_upgrade()
    assert abs(c.rate - 0.5) < abs(r - 0.5)    # relaxed toward prior


def test_adaptive_draft_bits_climb_is_lossless():
    """A hopeless 2-bit draft (0% acceptance on this config): the
    controller walks the draft up the precision ladder mid-generation
    — a metadata-only view swap — and the stream stays exactly plain
    greedy."""
    cfg, model, params = _tiny()
    prog = divide(params)
    tokens = jax.random.randint(jax.random.PRNGKey(13), (1, 8), 0,
                                cfg.vocab).astype(jnp.int32)
    steps = 16
    spec = SpeculativeEngine(
        model, prog, max_len=8 + steps + 9,
        spec=SpecConfig(draft_bits=2, k=None))
    plain = ProgressiveServer(model, prog, max_len=8 + steps + 9,
                              resident="quantized")
    for _ in range(prog.n_stages):
        spec.receive_stage()
        plain.receive_stage()
    spec.start({"tokens": tokens})
    plain.start({"tokens": tokens})
    np.testing.assert_array_equal(np.asarray(spec.decode(steps).tokens),
                                  np.asarray(plain.decode(steps).tokens))
    assert spec.controller.draft_bits > 2      # the climb happened
    assert spec.resident_report()["extra_draft_bytes"] == 0


def test_adaptive_engine_still_lossless():
    cfg, model, params = _tiny()
    prog = divide(params)
    tokens = jax.random.randint(jax.random.PRNGKey(9), (2, 8), 0,
                                cfg.vocab).astype(jnp.int32)
    steps = 12
    spec = SpeculativeEngine(model, prog, max_len=8 + steps + 9,
                             spec=SpecConfig(draft_bits=4, k=None))
    plain = ProgressiveServer(model, prog, max_len=8 + steps + 9,
                              resident="quantized")
    for _ in range(prog.n_stages):
        spec.receive_stage()
        plain.receive_stage()
    spec.start({"tokens": tokens})
    plain.start({"tokens": tokens})
    np.testing.assert_array_equal(np.asarray(spec.decode(steps).tokens),
                                  np.asarray(plain.decode(steps).tokens))


def test_session_speculative_events_and_parity():
    """Session.run_serving(speculative=...): accept-rate events land on
    the byte clock with draft/target effective bits, and the emitted
    stream equals a plain replay at the same per-token stages."""
    from repro.core import wire
    from repro.transmission import BandwidthTrace, Session

    cfg, model, params = _tiny()
    prog = divide(params)
    blob = wire.encode(prog)
    tokens = jax.random.randint(jax.random.PRNGKey(11), (1, 8), 0,
                                cfg.vocab).astype(jnp.int32)
    steps = 10
    session = Session(blob, BandwidthTrace.constant(1e6))
    res = session.run_serving(
        model, prog, decode_steps=steps, batch={"tokens": tokens},
        max_len=8 + steps + 9,
        speculative=SpecConfig(draft_bits=4, k=2))
    rounds = res.events_of("accept_round")
    assert rounds, "speculative session must log accept_round events"
    for e in rounds:
        assert {"k", "accepted", "rate", "stage",
                "effective_bits"} <= set(e.data)
        assert e.data["effective_bits"]["draft"] <= 4
    assert len(res.events_of("decode_step")) == steps
    # wire-fed store audit: zero extra draft bytes there too
    assert res.server.resident_report()["extra_draft_bytes"] == 0
    # parity vs the plain path replayed at the same stage schedule
    got = np.asarray(res.tokens)[0].tolist()
    assert got == _stage_replay(model, prog, tokens, res.stage_at_step)


def test_session_speculative_pool_events_and_parity():
    """Session.run_serving_pool(speculative=...): the flash-crowd pool
    runs draft+verify rounds, accept_round records land in the audit
    log, and each rid's stream equals the plain replay at its own
    per-token stage schedule."""
    from repro.core import wire
    from repro.transmission import BandwidthTrace, Session

    cfg, model, params = _tiny()
    prog = divide(params)
    blob = wire.encode(prog)
    prompts = [jax.random.randint(jax.random.PRNGKey(40 + i), (6,), 0,
                                  cfg.vocab).astype(jnp.int32)
               for i in range(3)]
    session = Session(blob, BandwidthTrace.constant(2e6))
    res = session.run_serving_pool(
        model, prog, prompts=prompts, max_new_tokens=6, n_slots=2,
        max_len=6 + 6 + 10, dispatch_window=1,
        speculative=SpecConfig(draft_bits=4, k=2))
    assert res.events_of("accept_round"), "pool must log accept records"
    pool = res.server
    assert isinstance(pool, SpeculativeSlotPool)
    assert pool.decode_cache_size() <= 2
    assert pool.resident_report()["extra_draft_bytes"] == 0
    for rid, p in enumerate(prompts):
        want = _stage_replay(model, prog, p, pool.stage_log[rid],
                             admit_stage=pool.admit_stage[rid])
        assert res.tokens[rid] == want, f"rid {rid}"
        assert len(res.tokens[rid]) == 6


def test_pool_mixed_budgets_freeze_finished_slots():
    """A small-budget request finishes mid-window and keeps riding
    rounds until flush; its position must FREEZE at its budget ceiling
    so `room` never collapses for co-resident slots — k stays full and
    the pool holds exactly two executables (the regression: an
    over-budget slot advancing ~k+1 per round blew through the max_len
    headroom and compiled clamped verify shapes)."""
    cfg, model, params = _tiny()
    prog = divide(params)
    budgets = [3, 12, 12]
    prompts = [jax.random.randint(jax.random.PRNGKey(50 + i), (8,), 0,
                                  cfg.vocab).astype(jnp.int32)
               for i in range(3)]
    spec = SpecConfig(draft_bits=4, k=4)
    pool = SpeculativeSlotPool(model, prog, n_slots=3,
                               max_len=8 + 12 + spec.k_max + 1,
                               spec=spec, dispatch_window=4)
    for _ in range(prog.n_stages):
        pool.receive_stage()
    for i, (p, b) in enumerate(zip(prompts, budgets)):
        pool.submit(PoolRequest(rid=i, prompt=p, max_new_tokens=b))
    out = pool.run()
    assert pool.decode_cache_size() == 2, \
        "over-budget slots must not clamp k into extra verify shapes"
    for rid, b in enumerate(budgets):
        assert len(out[rid]) == b
        srv = ProgressiveServer(model, prog, max_len=8 + 12 + spec.k_max + 1,
                                resident="quantized")
        for _ in range(prog.n_stages):
            srv.receive_stage()
        srv.start({"tokens": prompts[rid][None]})
        want = np.asarray(srv.decode(b).tokens)[0].tolist()
        assert out[rid] == want, f"rid {rid}"


# ---------------------------------------------------------------------------
# hardening: verify headroom is VALIDATED up front, never clamped
# ---------------------------------------------------------------------------

def test_construction_rejects_missing_verify_headroom():
    """The T-wide verify block writes k_max + 1 rows past the base
    position; a cache without that headroom used to clamp the write
    onto live KV rows silently. Both serving shapes must refuse to
    construct (mirroring SlotPoolEngine.submit's prompt+budget
    check)."""
    _, model, params = _tiny()
    prog = divide(params)
    spec = SpecConfig(draft_bits=4, k=4, k_max=4)
    with pytest.raises(ValueError, match="k_max"):
        SpeculativeEngine(model, prog, max_len=spec.k_max + 1, spec=spec)
    with pytest.raises(ValueError, match="k_max"):
        SpeculativeSlotPool(model, prog, n_slots=2,
                            max_len=spec.k_max + 1, spec=spec)
    # the floor itself constructs
    SpeculativeEngine(model, prog, max_len=spec.k_max + 2, spec=spec)


def test_start_and_decode_reject_insufficient_headroom():
    """Per-prompt and per-decode forms of the same invariant: start()
    needs prompt + k_max + 1 rows, decode() needs the final round's
    verify block to fit — both raise BEFORE any device work instead of
    letting the verify block run off the cache end."""
    _, model, params = _tiny()
    prog = divide(params)
    spec = SpecConfig(draft_bits=4, k=3, k_max=3)
    eng = SpeculativeEngine(model, prog, max_len=12, spec=spec)
    eng.receive_stage()
    long_prompt = jnp.zeros((1, 9), jnp.int32)  # 9 + 3 + 1 > 12
    with pytest.raises(ValueError, match="headroom"):
        eng.start({"tokens": long_prompt})
    eng.start({"tokens": jnp.zeros((1, 8), jnp.int32)})
    # pos 8: 8 + steps + k_max - 1 <= 12 allows steps <= 2
    with pytest.raises(ValueError, match="max_len"):
        eng.decode(3)
    eng.decode(2)


def test_pool_submit_rejects_request_without_headroom():
    """A request whose prompt + budget + k_max exceeds max_len used to
    be admitted and then clamp k near its budget end (extra verify
    shapes); now submit raises up front, like the plain pool's
    prompt+budget check."""
    _, model, params = _tiny()
    prog = divide(params)
    spec = SpecConfig(draft_bits=4, k=3, k_max=3)
    pool = SpeculativeSlotPool(model, prog, n_slots=2, max_len=16,
                               spec=spec)
    pool.receive_stage()
    with pytest.raises(ValueError, match="verify headroom"):
        pool.submit(PoolRequest(
            rid=0, prompt=jnp.zeros((8,), jnp.int32), max_new_tokens=6))
    pool.submit(PoolRequest(
        rid=1, prompt=jnp.zeros((8,), jnp.int32), max_new_tokens=5))


def test_tight_max_len_keeps_two_executables():
    """Generation driven to the exact end of the tightest legal cache:
    the 2-executable invariant must hold for the WHOLE session. Under
    the old end-of-generation clamp, k_eff = min(k, room) shrank on the
    final rounds and compiled one extra verify shape per distinct
    clamped k; validated headroom makes the clamp dead and this pins
    it."""
    cfg, model, params = _tiny()
    prog = divide(params)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 8), 0,
                                cfg.vocab).astype(jnp.int32)
    steps = 10
    spec_cfg = SpecConfig(draft_bits=4, k=3, k_max=3)
    # tightest max_len decode() accepts: prompt + steps + k_max - 1
    max_len = 8 + steps + spec_cfg.k_max - 1
    spec = SpeculativeEngine(model, prog, max_len=max_len, spec=spec_cfg)
    plain = ProgressiveServer(model, prog, max_len=8 + steps,
                              resident="quantized")
    for _ in range(prog.n_stages):
        spec.receive_stage()
        plain.receive_stage()
    spec.start({"tokens": tokens})
    plain.start({"tokens": tokens})
    got = np.asarray(spec.decode(steps).tokens)
    want = np.asarray(plain.decode(steps).tokens)
    np.testing.assert_array_equal(got, want)
    assert spec.decode_cache_size() == 2, \
        "end-of-generation rounds must not compile clamped verify shapes"


def test_pool_tight_max_len_keeps_two_executables():
    """Pool analogue: budgets met exactly against the tightest max_len
    submit() accepts (prompt + budget + k_max), full token identity,
    two executables across the whole run."""
    cfg, model, params = _tiny()
    prog = divide(params)
    steps = 8
    spec_cfg = SpecConfig(draft_bits=4, k=3, k_max=3)
    prompts = [jax.random.randint(jax.random.PRNGKey(60 + i), (8,), 0,
                                  cfg.vocab).astype(jnp.int32)
               for i in range(3)]
    max_len = 8 + steps + spec_cfg.k_max
    pool = SpeculativeSlotPool(model, prog, n_slots=2, max_len=max_len,
                               spec=spec_cfg, dispatch_window=2)
    for _ in range(prog.n_stages):
        pool.receive_stage()
    for i, p in enumerate(prompts):
        pool.submit(PoolRequest(rid=i, prompt=p, max_new_tokens=steps))
    out = pool.run()
    assert pool.decode_cache_size() == 2, \
        "budget-end rounds must not clamp k into extra verify shapes"
    for rid, p in enumerate(prompts):
        srv = ProgressiveServer(model, prog, max_len=8 + steps,
                                resident="quantized")
        for _ in range(prog.n_stages):
            srv.receive_stage()
        srv.start({"tokens": p[None]})
        want = np.asarray(srv.decode(steps).tokens)[0].tolist()
        assert out[rid] == want, f"rid {rid}"
        assert len(out[rid]) == steps
