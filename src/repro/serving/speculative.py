"""Self-speculative progressive decoding: the precision ladder as a
draft model.

The paper's core asset is that every prefix of the transmitted file is
itself a working approximate model. The quantized-resident engines
(PR 3/4) made those approximations live; this module makes them *pay
rent*: a truncated-precision view of the **same** PlaneStore
accumulators (``PlaneStore.quantized_leaves(bits=b)`` — a deferred
plane mask plus a recomputed eq.-(5) affine, sharing every uint buffer
with the target view) drafts k greedy tokens, and the full-received-
bits view verifies the whole draft in ONE chunked pass
(``model.verify_step`` -> ``ops.verify_attention``). Output is
token-identical to plain greedy decode at every precision stage: the
verify logits at a draft row equal what sequential target decode would
have produced there, so accepted drafts plus the correction token ARE
the plain greedy stream (losslessness, pinned by tests).

Speculation round (per slot; batched and ragged across slots):

    last ──draft──► d1..dk        (k decode_steps, draft view,
      │                            draft K/V written in place)
      └──[last,d1..dk]──verify──► g0..gk = target greedy per row
                                  (ONE verify_step; target K/V
                                   overwrites the draft's rows)
    accept a = longest prefix with d_{t+1} == g_t
    emit g0..ga  (a accepted drafts + 1 correction/bonus token)
    next round feeds g_a at pos + a + 1 — rejected rows are never
    rolled back, later rounds just overwrite them (zero cache copies;
    ring caches are over-allocated by ``k_max + 1`` slots so
    speculative writes can never clobber live window entries).

Cost shape: the draft pass reads the same accumulators as the target
(zero extra weight bytes is the point), so the win is *batching*: one
verify pass scores k+1 tokens in a single weight/cache sweep and the
host syncs once per round instead of once per token. On TPU the verify
kernel amortizes the whole KV cache read over the draft block; on this
CPU container the same effect shows up as round-level dispatch/sync
amortization (see ``benchmarks/speculative_decode.py`` for the honest
accounting).

Both serving shapes are covered: :class:`SpeculativeEngine` is the
lock-stepped single stream (slots start together, then run *ragged* —
each slot accepts a different number of drafts per round);
:class:`SpeculativeSlotPool` is the continuous-batching pool where
admissions, evictions and precision upgrades interleave with
speculation rounds. Upgrades refresh BOTH views from the same store
(metadata only) and change nothing static — zero recompiles
mid-speculation; exactly two decode executables exist (the draft's
``decode_step`` and the target's ``verify_step``).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as _obs
from repro.core.policy import SpeculationController
from repro.models.common import quantized_resident_eligible
from repro.serving.engine import (PoolStepStats, ProgressiveServer,
                                  SlotPoolEngine, resident_report)


@dataclasses.dataclass
class SpecConfig:
    """How to speculate. ``k=None`` hands draft-length control to an
    adaptive :class:`~repro.core.policy.SpeculationController` (k then
    moves on a power-of-two ladder with the observed acceptance rate,
    and collapses to 0 while the download hasn't passed ``draft_bits``
    yet); a fixed integer pins it (the benchmark sweeps do this —
    each distinct k compiles one draft/verify executable pair)."""

    draft_bits: int = 4
    k: int | None = None
    k_max: int = 8

    def __post_init__(self):
        if self.k is not None and self.k < 0:
            raise ValueError(f"k must be >= 0, got {self.k}")
        if self.k is not None and self.k > self.k_max:
            # honor the requested draft length: k_max sizes the ring
            # margins and max_len headroom, so it must cover k
            self.k_max = self.k

    def make_controller(self) -> SpeculationController:
        k0 = self.k if self.k is not None else min(4, self.k_max)
        return SpeculationController(draft_bits=self.draft_bits,
                                     k_max=self.k_max, k_init=max(k0, 1))


@dataclasses.dataclass
class SpeculativeResult:
    """Outcome of a speculative generation. ``tokens`` is the plain
    greedy stream (B, steps); speculation internals ride alongside."""

    tokens: Any
    stage_log: list          # per slot: stage at each emitted token
    upgrades: list           # (min emitted tokens, new stage)
    accept_rounds: list      # per round: dict(k, accepted, rate, stage)
    rounds: int = 0
    drafted: int = 0         # draft tokens proposed (active slots only)
    accepted: int = 0        # draft tokens accepted
    wall_s: float = 0.0
    ttft_s: float = 0.0

    @property
    def stage_at_step(self):
        """Lock-step view (slot 0's log) for plain-path compatibility."""
        return self.stage_log[0] if self.stage_log else []

    @property
    def acceptance_rate(self) -> float:
        return self.accepted / self.drafted if self.drafted else 0.0


def _verify_and_accept(model, params, caches, tokens, pos):
    """One target verify pass + on-device acceptance.

    tokens: (B, T) = last accepted token ++ k drafts; pos: (B,) base
    positions (negative = inactive slot). Returns ``(g, acc, nxt,
    caches)``: ``g[:, t]`` is the target's greedy token after consuming
    ``tokens[:, :t+1]`` (the plain-greedy continuation), ``acc`` the
    per-slot count of accepted drafts (longest matching prefix), and
    ``nxt = g[:, acc]`` the correction/bonus token that seeds the next
    round. Everything stays on device; the host reads g/acc once per
    round."""
    logits, caches = model.verify_step(params, caches, tokens, pos)
    g = jnp.argmax(logits, axis=-1).astype(jnp.int32)          # (B, T)
    if tokens.shape[1] > 1:
        match = (tokens[:, 1:] == g[:, :-1]).astype(jnp.int32)  # (B, k)
        acc = jnp.cumprod(match, axis=1).sum(axis=1)            # (B,)
    else:
        acc = jnp.zeros(tokens.shape[:1], jnp.int32)
    nxt = jnp.take_along_axis(g, acc[:, None], axis=1)          # (B, 1)
    return g, acc, nxt, caches


class _SpeculativeMixin:
    """Draft-view plumbing shared by the single-stream engine and the
    slot pool: refresh both precision views from ONE store, count both
    executables, audit the zero-extra-bytes invariant."""

    _SSM_KINDS = frozenset({"mamba2", "mlstm", "slstm"})

    def _init_spec(self, spec: SpecConfig | None):
        cfg = self.model.cfg
        ssm = set(cfg.cycle + cfg.tail) & self._SSM_KINDS
        if ssm:
            raise NotImplementedError(
                f"speculative decoding is not supported for recurrent "
                f"blocks {sorted(ssm)}: their cumulative state has no "
                f"overwrite-only rollback (a rejected draft would need a "
                f"state snapshot per token — the opposite of the "
                f"zero-copy KV story)")
        self.spec = spec or SpecConfig()
        # The contiguous verify block writes T = k + 1 rows starting at
        # the base position, and the cache write drops rows past the
        # cache end: a cache without k_max + 1 rows of headroom past the
        # last decodable position would silently lose drafted KV instead
        # of failing. Enforce the floor at construction (mirroring
        # SlotPoolEngine.submit's prompt+budget check); start()/
        # submit() validate the per-prompt / per-request form.
        min_len = self.spec.k_max + 2
        if self.max_len < min_len:
            raise ValueError(
                f"max_len {self.max_len} < k_max + 2 = {min_len}: the "
                f"T-wide verify write needs k_max + 1 rows of headroom "
                f"past the base position, or it runs off the cache "
                f"end")
        self.controller = self.spec.make_controller()
        self.draft_params = None
        self._verify = jax.jit(self._meshed(
            lambda p, c, t, pos: _verify_and_accept(self.model, p, c, t, pos)),
            donate_argnums=1)
        self.accept_log: list[dict] = []
        if self.params is not None:
            self._refresh_params()

    # -- both views, one store --------------------------------------------
    # The TARGET view is also built in masked form (bits clamped per
    # leaf to its full width — a value-level no-op) so draft and target
    # pytrees share one treedef: a degenerate k = 0 round then runs the
    # target through the SAME decode executable the draft steps use,
    # and the engine holds exactly two executables for a fixed k.
    _FULL_BITS = 1 << 10

    def current_draft_bits(self) -> int:
        """Fixed-k engines pin the draft precision; adaptive engines
        follow the controller, which climbs the precision ladder when
        rejection persists at the shortest drafts."""
        return (self.spec.draft_bits if self.spec.k is not None
                else self.controller.draft_bits)

    def _refresh_params(self) -> None:
        b = self.current_draft_bits()
        self._draft_bits_live = b
        if self._receiver is not None:
            self.params = self._receiver.materialize_resident(
                bits=self._FULL_BITS)
            self.draft_params = self._receiver.materialize_resident(bits=b)
        else:
            self.params = self.state.materialize_resident(
                quantized_resident_eligible, bits=self._FULL_BITS)
            self.draft_params = self.state.materialize_resident(
                quantized_resident_eligible, bits=b)

    def _sync_draft_view(self) -> None:
        """Re-point the draft view when the controller moved draft_bits
        — a metadata-only refresh of the SAME accumulators (traced
        keep_bits/affine), so it never recompiles anything."""
        if self.current_draft_bits() != getattr(self, "_draft_bits_live",
                                                None):
            self._refresh_params()

    def receive_stage(self) -> None:
        """A stage upgrade changes the draft/target gap, so acceptance
        evidence gathered against the old gap is stale — relax the
        controller's EWMA toward its prior (both serving shapes route
        their upgrades through here)."""
        super().receive_stage()
        self.controller.on_upgrade()

    def _record_accept(self, rec: dict) -> dict:
        """Accept-round chokepoint: the legacy ``accept_log`` record
        plus registry views over the same values (round counter,
        accepted-per-slot histogram, controller-rate gauge)."""
        self.accept_log.append(rec)
        if _obs.enabled():
            engine = type(self).__name__
            reg = _obs.get_registry()
            reg.counter("spec_rounds_total",
                        "speculative accept rounds").inc(engine=engine)
            acc = rec["accepted"]
            for a in (acc if isinstance(acc, list) else [acc]):
                reg.histogram("spec_accepted_per_round",
                              "accepted drafts per slot per round").observe(
                                  a, engine=engine)
            reg.gauge("spec_accept_rate",
                      "controller acceptance EWMA").set(
                          rec["rate"], engine=engine)
        return rec

    def received_bits_now(self) -> int:
        """Min effective precision across the store's tensors — what the
        controller compares against draft_bits."""
        store = (self._receiver.store if self._receiver is not None
                 else self.state.store)
        if store is None or store.n_tensors == 0:
            return 0
        return min(store.effective_bits(i) for i in range(store.n_tensors))

    def choose_k(self) -> int:
        if self.spec.k is not None:
            if self.received_bits_now() <= self.spec.draft_bits:
                return 0  # no precision gap: drafting buys nothing
            return min(self.spec.k, self.spec.k_max)
        return self.controller.choose_k(self.received_bits_now())

    # -- one speculation round (shared by both serving shapes) -------------
    def _run_round(self, caches, last_tok, pos, k_eff: int):
        """Draft k_eff tokens from the truncated view, then verify the
        whole block with the target view — or, degenerate (k_eff == 0),
        one plain decode step through the SAME executable the draft
        uses. Returns ``(g, acc, nxt, caches)`` with everything still
        on device. This is the single home of the round protocol: draft
        step j feeds block token j at position pos + j, and the verify
        overwrites every drafted slot with target K/V."""
        if k_eff == 0:
            logits, caches = self._decode(self.params, caches, last_tok, pos)
            self._count_kv_step("decode")
            g = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
            return g, jnp.zeros(g.shape[:1], jnp.int32), g, caches
        toks = [last_tok]
        cur = last_tok
        for j in range(k_eff):
            # keep inactive slots' sentinel negative: -1 + j would walk
            # back into valid range and write garbage K/V into a row
            # the invariant says stays untouched
            pj = jnp.where(pos >= 0, pos + j, jnp.int32(-1))
            logits, caches = self._decode(self.draft_params, caches, cur, pj)
            self._count_kv_step("decode")
            cur = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
            toks.append(cur)
        self._count_kv_step("verify")
        return self._verify(self.params, caches,
                            jnp.concatenate(toks, axis=1), pos)

    # -- audits ------------------------------------------------------------
    def decode_cache_size(self) -> int:
        """Draft ``decode_step`` entries + target ``verify_step``
        entries. Exactly 2 for a fixed k: ONE decode executable —
        shared by every draft step AND by degenerate k = 0 rounds,
        because the target view is built with the same treedef as the
        draft view — plus ONE verify executable; both survive every
        precision upgrade. Adaptive k adds one verify entry per
        distinct ladder value (T is a static shape)."""
        return self._decode._cache_size() + self._verify._cache_size()

    def resident_report(self) -> dict:
        """Audit target + draft views TOGETHER: the draft shares every
        weight buffer with the target (``aliased_leaves``), so
        ``extra_draft_bytes`` — resident weight bytes beyond the target
        view alone — must be 0. ``effective_bits`` tells the two views
        apart per leaf."""
        if self.params is None or self.draft_params is None:
            raise RuntimeError("no planes received yet")
        target = resident_report(self.params)
        both = resident_report({"target": self.params,
                                "draft": self.draft_params})
        both["extra_draft_bytes"] = (
            both["quantized_bytes"] + both["fp_bytes"]
            - target["quantized_bytes"] - target["fp_bytes"])
        return both


class SpeculativeEngine(_SpeculativeMixin, ProgressiveServer):
    """Single-stream self-speculative server (quantized-resident only:
    the draft IS a second metadata view over the resident accumulators).

    Slots start lock-stepped at the prompt and immediately go *ragged*:
    each slot accepts a different number of drafts per round, so
    positions are per-slot ``(B,)`` from round one — the same ragged
    machinery the continuous-batching kernels already speak. A slot
    that has emitted ``steps`` tokens is masked out (``pos = -1``)
    while the rest finish."""

    def __init__(self, model, prog, max_len: int, receiver=None,
                 spec: SpecConfig | None = None, mesh=None):
        super().__init__(model, prog, max_len, receiver=receiver,
                         resident="quantized", mesh=mesh)
        self._init_spec(spec)

    def start(self, batch: dict) -> None:
        if self.params is None:
            raise RuntimeError("no planes received yet — call receive_stage()")
        prompt_len = int(batch["tokens"].shape[1])
        if prompt_len + self.spec.k_max + 1 > self.max_len:
            raise ValueError(
                f"prompt of {prompt_len} tokens leaves no verify "
                f"headroom: needs prompt + k_max + 1 = "
                f"{prompt_len + self.spec.k_max + 1} <= max_len "
                f"{self.max_len}")
        last_logits, caches = self._prefill(self.params, batch)
        # ring caches over-allocated by the max draft block so verify
        # writes never clobber live window entries
        self.caches = self.model.grow_caches(
            caches, self.max_len, ring_margin=self.spec.k_max + 1,
            pos=prompt_len)
        self.pos = prompt_len
        self.last_logits = last_logits
        self._pos_np = np.full((last_logits.shape[0],), prompt_len, np.int64)
        self._first_tok = jnp.argmax(last_logits, axis=-1).astype(
            jnp.int32)[:, None]
        self._decoded = False

    def decode(self, steps: int, *,
               stage_arrival: Callable[[int], bool] | None = None,
               on_round: Callable[[dict], None] | None = None,
               **_ignored) -> SpeculativeResult:
        """Greedy-decode ``steps`` tokens per slot through speculation
        rounds. ``stage_arrival(emitted)`` is consulted between rounds
        (the speculative analogue of the plain path's between-steps
        check); ``on_round`` sees each round's accept record — the
        Session uses it to stamp accept-rate events on the byte clock.

        One-shot per :meth:`start`: slots finish ragged and fast slots'
        surplus tokens are discarded, so there is no coherent state to
        resume a second ``decode`` from (unlike the lock-stepped plain
        path, which chains on ``last_logits``)."""
        if getattr(self, "_decoded", True):
            raise RuntimeError(
                "speculative decode is one-shot per start(): surplus "
                "tokens of fast slots are discarded at the end of a "
                "run, so continuing would skip them — call start() "
                "again to begin a new generation")
        # validate BEFORE consuming the one-shot: a rejected call must
        # leave the started generation decodable with a legal step count
        need = int(self._pos_np.max()) + steps + self.spec.k_max - 1
        if need > self.max_len:
            raise ValueError(
                f"decoding {steps} steps needs max_len >= prompt + "
                f"steps + k_max - 1 = {need}, got {self.max_len} (the "
                f"final rounds' verify blocks would run off the cache "
                f"end)")
        self._decoded = True
        B = int(self._first_tok.shape[0])
        emitted: list[list[int]] = [[] for _ in range(B)]
        stage_log: list[list[int]] = [[] for _ in range(B)]
        upgrades: list[tuple[int, int]] = []
        t_start = time.perf_counter()
        # the prefill's argmax is the first plain-greedy token
        first = np.asarray(self._first_tok)[:, 0]
        ttft = time.perf_counter() - t_start
        for b in range(B):
            emitted[b].append(int(first[b]))
            stage_log[b].append(self.stage)
        last_tok = self._first_tok
        rounds = drafted = accepted_total = 0
        n_rounds_guard = steps * (B + 1) + 8
        while min(len(e) for e in emitted) < steps:
            if rounds > n_rounds_guard:
                raise AssertionError("speculative decode did not converge")
            done = min(len(e) for e in emitted)
            if stage_arrival and self.stage < self.prog.n_stages \
                    and stage_arrival(done):
                self.receive_stage()  # relaxes the controller EWMA too
                upgrades.append((done, self.stage))
            self._sync_draft_view()
            active = np.array([len(e) < steps for e in emitted])
            pos_masked = np.where(active, self._pos_np, -1)
            # headroom was validated at start()/decode(): every active
            # slot can take a full k_max-draft verify block, so k never
            # shrinks at the end of generation and no extra verify
            # shape ever compiles (the 2-executable invariant holds
            # for the whole session)
            k_eff = self.choose_k()
            pos_dev = jnp.asarray(pos_masked, jnp.int32)
            g, acc, nxt, self.caches = self._run_round(
                self.caches, last_tok, pos_dev, k_eff)
            acc_np = np.asarray(acc)
            g_np = np.asarray(g)                   # host sync, once/round
            for b in range(B):
                if not active[b]:
                    continue
                take = int(acc_np[b]) + 1
                emitted[b].extend(int(t) for t in g_np[b, :take])
                stage_log[b].extend([self.stage] * take)
                self._pos_np[b] += take
            last_tok = nxt
            n_active = int(active.sum())
            drafted += k_eff * n_active
            accepted_total += int(acc_np[active].sum())
            self.controller.update(int(acc_np[active].sum()),
                                   k_eff * n_active)
            rec = self._record_accept(
                {"round": rounds, "k": k_eff,
                 "accepted": [int(a) for a in acc_np[active]],
                 "rate": self.controller.rate, "stage": self.stage,
                 "emitted": [len(e) for e in emitted]})
            if on_round is not None:
                on_round(rec)
            rounds += 1
        wall = time.perf_counter() - t_start
        self.last_logits = None  # the plain path's handle is stale now
        if _obs.enabled():
            reg = _obs.get_registry()
            reg.histogram("engine_ttft_s",
                          "wall seconds to first token value").observe(
                              ttft, engine="SpeculativeEngine")
            reg.counter("engine_tokens_total",
                        "tokens emitted by serving engines").inc(
                            steps * B, engine="SpeculativeEngine")
            _obs.get_tracer().record("decode_window", wall_s=wall,
                                     engine="SpeculativeEngine")
        return SpeculativeResult(
            tokens=jnp.asarray(np.array([e[:steps] for e in emitted],
                                        np.int32)),
            stage_log=[s[:steps] for s in stage_log],
            upgrades=upgrades,
            accept_rounds=list(self.accept_log[-rounds:] if rounds else []),
            rounds=rounds, drafted=drafted, accepted=accepted_total,
            wall_s=wall, ttft_s=ttft)


class SpeculativeSlotPool(_SpeculativeMixin, SlotPoolEngine):
    """Continuous-batching speculation: one draft chain + one verify
    pass serve EVERY occupied slot per round, ragged positions and all.
    Admission follows the base pool (chunked by default: the prompt
    streams into the pooled caches block by block, the slot joining
    draft rounds once its last chunk lands and its first greedy token —
    captured device-side — being emitted at the next flush; batch-1
    fallback prefills at admission, ring caches grown by the
    speculative margin, first token emitted immediately). Budget/eos
    eviction happens at flush, where the per-round acceptance counts
    become host-visible. One draft executable + one verify executable
    across every admission, eviction and precision upgrade."""

    def __init__(self, model, prog, *, n_slots: int, max_len: int,
                 receiver=None, spec: SpecConfig | None = None,
                 dispatch_window: int = 4, eos_id: int | None = None,
                 chunked_prefill: bool | None = None,
                 prefill_chunk: int = 8,
                 prefill_buckets: bool = True,
                 double_buffer: bool = True,
                 mesh=None):
        spec = spec or SpecConfig()
        super().__init__(model, prog, n_slots=n_slots, max_len=max_len,
                         receiver=receiver, resident="quantized",
                         dispatch_window=dispatch_window, eos_id=eos_id,
                         ring_margin=spec.k_max + 1,
                         chunked_prefill=chunked_prefill,
                         prefill_chunk=prefill_chunk,
                         prefill_buckets=prefill_buckets,
                         double_buffer=double_buffer,
                         mesh=mesh)
        self._init_spec(spec)
        # per-slot position ceiling (prompt + budget - 1): a slot whose
        # budget is met keeps riding rounds until flush evicts it, but
        # its pos freezes here — otherwise it would keep advancing past
        # the verify headroom `submit` validated for it
        self._pos_bound = jnp.full((n_slots,), max_len, jnp.int32)
        # chunked admissions whose first token awaits host emission:
        # (slot, rid, stage at prefill completion)
        self._deferred_first: list[tuple[int, int, int]] = []

    # -- admission ----------------------------------------------------------
    def _validate_request(self, req) -> None:
        super()._validate_request(req)
        prompt = np.asarray(req.prompt)
        if prompt.shape[0] + req.max_new_tokens + self.spec.k_max \
                > self.max_len:
            # the last round at pos = prompt + budget - 1 verify-writes
            # k_max more rows; past max_len the write would drop them
            # (and a shrunken k would compile a second verify shape)
            raise ValueError(
                f"request needs {prompt.shape[0]} prompt + "
                f"{req.max_new_tokens} new tokens + {self.spec.k_max} "
                f"verify headroom > max_len {self.max_len}")

    def _post_admit(self, slot: int, req, prompt_len: int) -> None:
        self._pos_bound = self._pos_bound.at[slot].set(
            prompt_len + req.max_new_tokens - 1)

    def _grow_admitted(self, caches, prompt_len: int):
        return self.model.grow_caches(
            caches, self.max_len, ring_margin=self._ring_margin,
            pos=prompt_len)

    def _post_admit_batch1(self, slot: int, req, last_logits,
                           prompt_len: int) -> None:
        first = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
        self._last_tok = self._last_tok.at[slot].set(first)
        # the prefill argmax is the request's first greedy token,
        # emitted right at admission (the plain pool emits it on the
        # request's first batched step instead — same token)
        self._note_first_token(req.rid)
        self.outputs[req.rid].append(int(first[0]))
        self.stage_log[req.rid].append(self.stage)
        self.slots[slot].dispatched = 1
        if req.max_new_tokens == 1:
            self._evict(slot)

    def _on_prefill_complete(self, slot: int) -> None:
        # the chunk step captured the first greedy token in _first_cap
        # device-side; emission waits for the next flush (no host sync
        # mid-window), chronologically before any round that includes
        # this slot — rounds only snapshot it from here on
        self._deferred_first.append((slot, self.slots[slot].rid,
                                     self.stage))

    # -- one speculation round for the whole pool ---------------------------
    def step(self) -> dict[int, int]:
        """One scheduling tick: advance chunked prefills by one block,
        then run one batched speculation round — k draft decode_steps +
        one verify pass over every decoding slot. Free and mid-prefill
        slots ride along masked (``pos = -1``). Token values stay on
        device until :meth:`flush`."""
        if self.params is None:
            raise RuntimeError("no planes received yet — call receive_stage()")
        if self._win_t0 is None:
            self._win_t0 = time.perf_counter()
        self._prefill_tick()
        snapshot = self.active_rids()
        active = np.array([i in snapshot for i in range(self.n_slots)])
        if not active.any():
            return snapshot
        self._sync_draft_view()
        # submit() validated prompt + budget + k_max <= max_len for
        # every admitted request, so a full k-draft verify block always
        # fits — k never shrinks at the end of a request's budget and
        # the 2-executable invariant holds across the pool's lifetime
        k_eff = self.choose_k()
        g, acc, nxt, self.caches = self._run_round(
            self.caches, self._last_tok, self.pos, k_eff)
        act_dev = jnp.asarray(active)
        self.pos = jnp.where(act_dev,
                             jnp.minimum(self.pos + acc + 1,
                                         self._pos_bound),
                             self.pos)
        self._last_tok = jnp.where(act_dev[:, None], nxt, self._last_tok)
        self._pending.append((g, acc, snapshot, self.stage, k_eff))
        self._step_count += 1
        return snapshot

    def _flush_deferred_first(self) -> int:
        """Emit the captured first token of every chunk-admitted
        request whose prefill completed since the last flush. Runs
        BEFORE round distribution: the first token chronologically
        precedes every round that snapshots the slot."""
        if not self._deferred_first:
            return 0
        first_np = np.asarray(self._first_cap)  # host sync (flush-time)
        emitted = 0
        for slot, rid, stage in self._deferred_first:
            s = self.slots[slot]
            if s.free or s.rid != rid:
                continue
            tok = int(first_np[slot])
            self._note_first_token(rid)
            self.outputs[rid].append(tok)
            self.stage_log[rid].append(stage)
            s.dispatched += 1
            emitted += 1
            if s.dispatched >= s.budget or \
                    (self.eos_id is not None and tok == self.eos_id):
                self._evict(slot)
        self._deferred_first.clear()
        return emitted

    def flush(self) -> PoolStepStats | None:
        """Read the in-flight rounds' tokens + acceptance, distribute
        them, and do the budget/eos bookkeeping that the plain pool
        does at dispatch time (speculation only learns how many tokens
        a round produced when the acceptance counts land)."""
        emitted = self._flush_deferred_first()
        if not self._pending:
            # budget-1 admissions can retire a request without any
            # in-flight round; still surface them as completed
            self.completed |= self._retired
            self._retired.clear()
            return None
        jax.block_until_ready(self._last_tok)
        wall = time.perf_counter() - (self._win_t0 or time.perf_counter())
        for g, acc, snapshot, stage, k_eff in self._pending:
            g_np = np.asarray(g)
            acc_np = np.asarray(acc)
            self._record_accept({
                "k": k_eff, "accepted": [int(acc_np[s]) for s in snapshot],
                "rate": self.controller.rate, "stage": stage})
            self.controller.update(
                int(sum(acc_np[s] for s in snapshot)),
                k_eff * len(snapshot))
            for slot, rid in snapshot.items():
                if rid in self.completed or rid in self._retired:
                    continue  # evicted while this round was in flight
                s = self.slots[slot]
                take = min(int(acc_np[slot]) + 1,
                           max(s.budget - s.dispatched, 0))
                s.dispatched += take
                for tok in g_np[slot, :take]:
                    self.outputs[rid].append(int(tok))
                    self.stage_log[rid].append(stage)
                    emitted += 1
                    if self.eos_id is not None and int(tok) == self.eos_id:
                        self._evict(slot)
                        break
                if not s.free and s.rid == rid and \
                        s.dispatched >= s.budget:
                    self._evict(slot)
        self.completed |= self._retired
        self._retired.clear()
        stats = PoolStepStats(steps=len(self._pending), wall_s=wall,
                              tokens_emitted=emitted,
                              upgrades=self._win_upgrades,
                              upgrade_enqueue_s=self._win_upgrade_enqueue_s,
                              prefill_ticks=self._win_prefill_ticks)
        return self._record_window(stats)
