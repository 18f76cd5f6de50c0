"""Chunked ragged prefill: the ISSUE-6 acceptance surface.

1. Token identity: chunked admission (prompt KV streamed into pooled
   cache rows ``prefill_chunk`` tokens at a time, interleaved with
   decode) is token-identical to the legacy batch-1 prefill + grow +
   slot-write path, per slot and per precision stage, for dense, MoE,
   recurrent (xLSTM) and sliding-window (ring cache) archs — with
   exactly ONE decode executable and ONE prefill-chunk executable.
2. Isolation: a chunk tick and the masked decode steps it interleaves
   with never touch another slot's cache rows (byte identity for idle
   slots); ring caches wrap correctly mid- and post-prefill.
3. Zero copies: the admit path performs no ``grow_caches`` and traces
   no cache-sized transpose/copy/concatenate/gather (jaxpr regression
   mirroring the speculative rollback pin).
4. Validation: malformed requests (2-D prompts, bad extras) raise at
   ``submit`` before any device work; batch-1 bucketing compiles
   O(log max_len) prefill variants, not one per distinct length.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.progressive import divide
from repro.models.model import build_model
from repro.serving.engine import PoolRequest, SlotPoolEngine

CHUNK = 4  # small so 3-9 token prompts still span multiple blocks

ARCH_OVERRIDES = {
    "olmo-1b": {},                                    # dense attention
    "dbrx-132b": {"n_experts": 2, "top_k": 1},        # MoE
    "xlstm-125m": {},                                 # slstm + mlstm
    "mixtral-8x22b": {"n_experts": 2, "top_k": 1,
                      "window": 8},                   # swa_moe ring caches
    # gemma3-like mix: positional global caches carried through the
    # layer loop (and one as a tail block), ring caches scanned over
    "gemma3-27b": {"cycle": ("global", "swa"), "n_layers": 5,
                   "window": 8},
}


def _build(arch, seed=0, **over):
    base = dict(n_layers=2, d_model=32, d_ff=64, vocab=64,
                n_heads=2, n_kv=2)
    base.update(ARCH_OVERRIDES[arch])
    base.update(over)
    cfg = get_config(arch).reduced(**base)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    return cfg, model, params, divide(params)


def _prompts(cfg, lengths, seed=1):
    return [jax.random.randint(jax.random.PRNGKey(seed + i), (L,), 0,
                               cfg.vocab).astype(jnp.int32)
            for i, L in enumerate(lengths)]


def _run_pool(model, prog, prompts, *, steps, stage, chunked, max_len,
              n_slots=3, dispatch_window=2):
    pool = SlotPoolEngine(model, prog, n_slots=n_slots, max_len=max_len,
                          dispatch_window=dispatch_window,
                          chunked_prefill=chunked,
                          prefill_chunk=CHUNK,
                          prefill_buckets=False)
    for _ in range(stage):
        pool.receive_stage()
    for i, p in enumerate(prompts):
        pool.submit(PoolRequest(rid=i, prompt=p, max_new_tokens=steps))
    out = pool.run()
    return pool, out


# ---------------------------------------------------------------------------
# acceptance: chunked == batch-1, per slot, per stage, one executable
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", sorted(ARCH_OVERRIDES))
def test_chunked_equals_batch1_per_stage(arch):
    """For each precision stage, a pool admitting via chunked prefill
    must emit EXACTLY the token stream of the legacy batch-1 admission
    pool — ragged lengths spanning multiple chunks, more requests than
    slots (queueing), one decode + one chunk executable."""
    cfg, model, params, prog = _build(arch)
    steps = 4
    prompts = _prompts(cfg, [5, 9, 3, 8])
    max_len = 9 + steps
    for stage in (1, prog.n_stages):
        legacy, out_l = _run_pool(model, prog, prompts, steps=steps,
                                  stage=stage, chunked=False,
                                  max_len=max_len)
        chunked, out_c = _run_pool(model, prog, prompts, steps=steps,
                                   stage=stage, chunked=True,
                                   max_len=max_len)
        assert chunked._tick_count > 0, "chunked pool must consume chunks"
        assert legacy._tick_count == 0
        assert chunked.decode_cache_size() == 1
        assert chunked.prefill_cache_size() == 1, \
            "4 distinct prompt lengths must share one chunk executable"
        for rid in range(len(prompts)):
            assert out_c[rid] == out_l[rid], f"{arch} stage {stage} rid {rid}"
            assert chunked.stage_log[rid] == legacy.stage_log[rid]


def test_ring_wraparound_long_decode():
    """Sliding-window ring caches: chunked prefill writes through the
    over-allocated ring (margin = prefill_chunk) and a long decode
    wraps it repeatedly; stream equality with the batch-1 pool pins
    both the wraparound arithmetic and the prefill ring writes."""
    cfg, model, params, prog = _build("mixtral-8x22b", seed=3)
    steps = 12  # decode positions cross the window-8 ring several times
    prompts = _prompts(cfg, [9, 6], seed=7)
    max_len = 9 + steps
    legacy, out_l = _run_pool(model, prog, prompts, steps=steps,
                              stage=prog.n_stages, chunked=False,
                              max_len=max_len, n_slots=2)
    chunked, out_c = _run_pool(model, prog, prompts, steps=steps,
                               stage=prog.n_stages, chunked=True,
                               max_len=max_len, n_slots=2)
    for rid in range(len(prompts)):
        assert out_c[rid] == out_l[rid], f"rid {rid}"


# ---------------------------------------------------------------------------
# isolation: idle slots are untouched, mid-prefill upgrades are sound
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["olmo-1b", "xlstm-125m"])
def test_idle_slot_rows_byte_identical(arch):
    """A pool serving ONE request must leave every other slot's cache
    rows byte-identical to their init state: chunk ticks mask idle
    lanes, and decode steps write NOTHING for pos = -1 slots (the
    regression: clamped writes used to scribble on row 0)."""
    cfg, model, params, prog = _build(arch)
    pool = SlotPoolEngine(model, prog, n_slots=3, max_len=16,
                          dispatch_window=2, chunked_prefill=True,
                          prefill_chunk=CHUNK, prefill_buckets=False)
    pool.receive_stage()
    before = [np.array(x) for x in jax.tree.leaves(pool.caches)]
    pool.submit(PoolRequest(rid=0, prompt=_prompts(cfg, [6])[0],
                            max_new_tokens=4))
    pool.run()
    after = jax.tree.leaves(pool.caches)
    for b, a in zip(before, after):
        a = np.array(a)
        for idle in (1, 2):
            # every cache leaf carries the slot axis first (tail) or
            # second (stacked cycles)
            rows = (a[idle], b[idle]) if a.shape[0] == 3 \
                else (a[:, idle], b[:, idle])
            np.testing.assert_array_equal(*rows)


def test_mid_prefill_upgrade_converges():
    """A precision upgrade landing BETWEEN chunk ticks of one prompt:
    the remaining chunks run at the new stage, the run converges, and
    the pool still holds one decode + one chunk executable. (Token
    parity with a fixed-stage replay is undefined here by design — the
    prompt's KV spans two precisions.)"""
    cfg, model, params, prog = _build("olmo-1b")
    steps = 4
    prompt = _prompts(cfg, [20])[0]  # 5 chunks of CHUNK=4
    pool = SlotPoolEngine(model, prog, n_slots=2, max_len=20 + steps,
                          dispatch_window=2, chunked_prefill=True,
                          prefill_chunk=CHUNK, prefill_buckets=False)
    pool.receive_stage()
    pool.submit(PoolRequest(rid=0, prompt=prompt, max_new_tokens=steps))
    pool.step(); pool.step()             # two chunks at stage 1
    assert 0 in pool._prefill_state      # still mid-prefill
    assert pool.upgrade_if_available()   # pull mode: advances one stage
    out = pool.run()
    assert len(out[0]) == steps
    assert pool.admit_stage[0] == 1      # first chunk's stage
    assert set(pool.stage_log[0]) == {2}  # decode ran post-upgrade
    assert pool.decode_cache_size() == 1
    assert pool.prefill_cache_size() == 1
    assert pool.upgrade_log and pool.upgrade_log[-1]["stage"] == 2


def test_speculative_pool_composes_with_chunked_prefill():
    """SpeculativeSlotPool over chunked admission: draft/verify rounds
    start from the chunk-installed first token and the stream equals
    the legacy-admission speculative pool's (which is itself pinned to
    plain greedy elsewhere)."""
    from repro.serving.speculative import SpecConfig, SpeculativeSlotPool

    cfg, model, params, prog = _build("olmo-1b")
    steps, spec = 6, SpecConfig(draft_bits=4, k=2)
    prompts = _prompts(cfg, [5, 9, 3], seed=9)
    max_len = 9 + steps + spec.k_max + 1
    outs = {}
    for chunked in (False, True):
        pool = SpeculativeSlotPool(model, prog, n_slots=2, max_len=max_len,
                                   spec=spec, dispatch_window=2,
                                   chunked_prefill=chunked,
                                   prefill_chunk=CHUNK)
        for _ in range(prog.n_stages):
            pool.receive_stage()
        for i, p in enumerate(prompts):
            pool.submit(PoolRequest(rid=i, prompt=p, max_new_tokens=steps))
        outs[chunked] = pool.run()
    for rid in range(len(prompts)):
        assert outs[True][rid] == outs[False][rid], f"rid {rid}"
        assert len(outs[True][rid]) == steps


# ---------------------------------------------------------------------------
# jaxpr + host regression: the admit path copies nothing cache-sized
# ---------------------------------------------------------------------------

def _collect_eqns(jaxpr):
    out, stack = [], [jaxpr]
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


def test_chunk_step_jaxpr_zero_cache_copies():
    """Tracing the chunk step must show no cache-sized transpose /
    copy / concatenate / gather — prompt KV lands via the same
    in-place row write decode uses, and each attention block writes
    its k and v exactly once per chunk, into the stack the layer loop
    carries."""
    cfg, model, params, prog = _build("olmo-1b")
    B, C = 3, CHUNK
    pool = SlotPoolEngine(model, prog, n_slots=B, max_len=16,
                          dispatch_window=2, chunked_prefill=True,
                          prefill_chunk=C, prefill_buckets=False)
    pool.receive_stage()
    jaxpr = jax.make_jaxpr(pool._chunk_step)(
        pool.params, pool.caches, jnp.zeros((B, C), jnp.int32),
        jnp.full((B, C), -1, jnp.int32), jnp.full((B,), -1, jnp.int32),
        pool.pos, pool.last_logits, pool._last_tok, pool._first_cap)
    cache_sizes = {int(np.prod(leaf.shape[-4:]))
                   for leaf in jax.tree.leaves(pool.caches)
                   if leaf.ndim >= 4}
    assert cache_sizes
    offenders, writes = [], 0
    for eqn in _collect_eqns(jaxpr.jaxpr):
        sized_out = any(v.aval.ndim >= 4
                        and int(np.prod(v.aval.shape[-4:])) in cache_sizes
                        for v in eqn.outvars if hasattr(v.aval, "shape"))
        if not sized_out:
            continue
        if eqn.primitive.name in ("transpose", "copy", "concatenate",
                                  "gather"):
            offenders.append((eqn.primitive.name,
                              [v.aval.shape for v in eqn.outvars]))
        if eqn.primitive.name in ("dynamic_update_slice", "scatter"):
            writes += 1
    assert not offenders, f"cache-sized copies in chunk_step: {offenders}"
    # the cycle scan traces one attention body: one k write + one v
    # write of all C rows (masked rows dropped, never clamped onto the
    # cache end the way a C-wide block update would be)
    assert writes == 2, writes


def test_steps_donate_the_cache():
    """Each chunk and decode step takes the cache that comes in and
    hands back the buffer it wrote in place: the input is donated, so
    the pool never holds two caches."""
    cfg, model, params, prog = _build("olmo-1b")
    pool = SlotPoolEngine(model, prog, n_slots=2, max_len=16,
                          dispatch_window=2, chunked_prefill=True,
                          prefill_chunk=CHUNK, prefill_buckets=False)
    pool.receive_stage()
    pool.submit(PoolRequest(rid=0, prompt=_prompts(cfg, [6])[0],
                            max_new_tokens=4))
    seen = []
    for _ in range(4):   # two chunk ticks, then decode steps
        before = jax.tree.leaves(pool.caches)
        pool.step()
        assert all(x.is_deleted() for x in before)
        seen.append(pool._tick_count)
    assert seen[-1] == 2 and pool._step_count >= 2
    assert not any(x.is_deleted() for x in jax.tree.leaves(pool.caches))


def test_chunked_admit_never_grows_caches(monkeypatch):
    """Chunked admission is host bookkeeping: no batch-1 prefill, no
    grow_caches, no per-leaf slot copy — the legacy admit path must be
    UNREACHABLE when chunking is on and the request has no extras."""
    cfg, model, params, prog = _build("olmo-1b")
    pool = SlotPoolEngine(model, prog, n_slots=2, max_len=16,
                          dispatch_window=2, chunked_prefill=True,
                          prefill_chunk=CHUNK)
    pool.receive_stage()

    def boom(*a, **k):
        raise AssertionError("grow_caches on the chunked admit path")

    monkeypatch.setattr(type(model), "grow_caches", boom)
    pool.submit(PoolRequest(rid=0, prompt=_prompts(cfg, [6])[0],
                            max_new_tokens=3))
    out = pool.run()
    assert len(out[0]) == 3


# ---------------------------------------------------------------------------
# validation + bucketing satellites
# ---------------------------------------------------------------------------

def test_submit_rejects_malformed_before_device_work():
    cfg, model, params, prog = _build("olmo-1b")
    pool = SlotPoolEngine(model, prog, n_slots=2, max_len=16,
                          dispatch_window=2)
    pool.receive_stage()
    good = _prompts(cfg, [4])[0]
    with pytest.raises(ValueError, match=r"one-dimensional"):
        pool.submit(PoolRequest(rid=0, prompt=good[None], max_new_tokens=2))
    with pytest.raises(ValueError, match=r"max_new_tokens"):
        pool.submit(PoolRequest(rid=1, prompt=good, max_new_tokens=0))
    with pytest.raises(ValueError, match=r">= 1 token"):
        pool.submit(PoolRequest(rid=2, prompt=good[:0], max_new_tokens=2))
    with pytest.raises(ValueError, match=r"unknown extras key"):
        pool.submit(PoolRequest(rid=3, prompt=good, max_new_tokens=2,
                                extras={"pixels": np.zeros((2, 2))}))
    # nothing was admitted, queued, or launched
    assert not pool.queue and not pool._prefill_state
    assert all(s.free for s in pool.slots)
    assert pool._tick_count == 0


def test_vision_extras_shape_rejected_before_prefill():
    cfg = get_config("llama32-vision-90b").reduced()
    model = build_model(cfg)
    prog = divide(model.init(jax.random.PRNGKey(0)))
    pool = SlotPoolEngine(model, prog, n_slots=2, max_len=16,
                          dispatch_window=2)
    pool.receive_stage()
    prompt = jnp.zeros((4,), jnp.int32)
    with pytest.raises(ValueError, match=r"per-request shape"):
        # batched (1, T, D) instead of per-request (T, D)
        pool.submit(PoolRequest(
            rid=0, prompt=prompt, max_new_tokens=2,
            extras={"vision_embeds": np.zeros(
                (1, cfg.vision_tokens, cfg.d_vision), np.float32)}))


def test_batch1_buckets_compile_log_many_prefills():
    """The legacy path with prefill_buckets pads prompts to power-of-two
    lengths with masked positions: 4 distinct lengths -> 2 compiled
    prefill shapes (unbucketed: 4), identical tokens."""
    cfg, model, params, prog = _build("olmo-1b")
    steps = 3
    prompts = _prompts(cfg, [3, 5, 6, 7], seed=13)
    max_len = 7 + steps
    outs, sizes = {}, {}
    for buckets in (False, True):
        pool = SlotPoolEngine(model, prog, n_slots=4, max_len=max_len,
                              dispatch_window=2, chunked_prefill=False,
                              prefill_buckets=buckets)
        for _ in range(prog.n_stages):
            pool.receive_stage()
        for i, p in enumerate(prompts):
            pool.submit(PoolRequest(rid=i, prompt=p, max_new_tokens=steps))
        outs[buckets] = pool.run()
        sizes[buckets] = pool.prefill_cache_size()
    assert sizes[False] == 4
    assert sizes[True] == 2, "lengths 3,5,6,7 must share buckets {4, 8}"
    for rid in range(len(prompts)):
        assert outs[True][rid] == outs[False][rid], f"rid {rid}"
