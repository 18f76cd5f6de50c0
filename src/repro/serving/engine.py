"""Progressive serving engine: single-stream server + continuous-
batching slot pool.

The deployment story of the paper, pod-side: a server starts with the
MSB planes of the weights, begins serving immediately, and upgrades
precision *in place* between decode steps as later planes arrive. The KV
cache and the compiled decode executable survive upgrades (weight
values change; shapes/dtypes don't), so an upgrade costs one integer
OR + dequantize — no recompilation, no cache invalidation, no request
draining. That is the TPU-serving analogue of the paper's Fig. 4
concurrent download/inference timeline.

Two engines share the precision machinery:

* :class:`ProgressiveServer` — the lock-stepped single stream (every
  slot at the same position). Kept for parity baselines, prefix tests
  and the Fig.-4 co-simulation.
* :class:`SlotPoolEngine` — continuous batching: a fixed pool of
  ``n_slots`` decode slots over ONE set of device caches in the flash
  kernel's native ``(B, Kh, S, hd)`` layout. Requests are admitted into
  free slots mid-flight (their prompt prefilled straight into the
  slot's cache region), finished requests are evicted, and every step
  is one batched ragged ``decode_step`` — per-slot ``(B,)`` positions,
  one compiled executable for the lifetime of the pool, upgrades
  applied between batched steps at zero recompiles.

Both engines dispatch **asynchronously**: the device is never host-
synced per token. Greedy sampling chains on device (argmax feeds the
next step), and the host only blocks on a bounded in-flight window
(``dispatch_window`` steps) before reading token values — so plane
ingest, admission bookkeeping and upgrade scheduling all overlap device
decode. ``sync=True`` restores the old block-per-token behavior (and
its per-token timing semantics) for comparable benchmarks.

The accumulators live in the shared PlaneStore: a stage upgrade is one
batched integer Pallas launch over the flat buffer. What the decode
step *sees* is governed by ``resident``:

* ``resident="fp"`` (paper): each upgrade re-dequantizes the dirty
  tensors into float leaves (incremental eq. 5) — a full fp copy of the
  model lives in HBM next to the accumulators.
* ``resident="quantized"`` (SLIDE-style): the live param pytree holds
  :class:`~repro.core.quantize.QuantizedTensor` *views* over the
  accumulators; eq. (5) runs fused into every matmul
  (``kernels/dequant_matmul``) and no fp weight buffer ever exists.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as _obs
from repro.core import wire
from repro.core.progressive import ProgressiveModel, ReceiverState, rebuild_params
from repro.core.quantize import QuantizedTensor
from repro.models.common import quantized_resident_eligible
from repro.models.model import Model
from repro.models.transformer import kv_path

RESIDENT_MODES = ("fp", "quantized")


@dataclasses.dataclass
class GenerationResult:
    tokens: Any           # (B, steps) generated token ids
    stage_at_step: list   # precision stage used for each decode step
    upgrades: list        # (step, stage) upgrade events
    per_step_s: list      # sync: measured per token; async: window_s/steps
    window_s: list = dataclasses.field(default_factory=list)
    #                    # (steps_in_window, wall_seconds) per flushed window
    ttft_s: float = 0.0   # wall time until the first token's value is on host
    tpot_s: float = 0.0   # total wall time / steps
    mode: str = "sync"    # "sync" (block per token) | "async" (windowed)


def resident_report(params) -> dict:
    """Leaf-type audit of a live param pytree: how many leaves are
    quantized-resident vs float, and the HBM bytes each side holds.
    ``quantized_bytes`` counts the uint accumulator views (what a
    quantized-resident server actually keeps for its weights);
    ``fp_bytes`` counts float leaves — for ``resident='quantized'``
    that is only the small non-matmul remainder (norms, gates, conv
    kernels), and the audit is exactly the acceptance check that no fp
    weight buffer exists.

    Buffers are counted ONCE per distinct array object: a speculative
    engine's draft view shares the target view's accumulators (and the
    fp remainder) verbatim, so auditing ``(target, draft)`` together
    shows zero extra resident weight bytes next to the target alone —
    ``aliased_leaves`` counts the shared ones. ``effective_bits`` maps
    each quantized leaf's path to its served precision
    ``min(received_bits, keep_bits)``, which is what tells a draft view
    apart from the full view (the buffers are identical)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=lambda x: isinstance(x, QuantizedTensor))
    n_q = n_fp = q_bytes = fp_bytes = meta_bytes = aliased = 0
    eff_bits: dict[str, int] = {}
    seen: set[int] = set()
    for path, leaf in flat:
        pstr = jax.tree_util.keystr(path)
        if isinstance(leaf, QuantizedTensor):
            n_q += 1
            if id(leaf.q) in seen:
                aliased += 1
            else:
                seen.add(id(leaf.q))
                q_bytes += leaf.q.size * leaf.q.dtype.itemsize
            for m in (leaf.lo, leaf.hi, leaf.scale, leaf.offset,
                      leaf.received_bits, leaf.keep_bits):
                if m is not None:
                    meta_bytes += np.size(m) * m.dtype.itemsize
            eff = leaf.bits
            if leaf.received_bits is not None:
                eff = int(np.max(np.asarray(leaf.received_bits)))
            if leaf.keep_bits is not None:
                eff = min(eff, int(np.max(np.asarray(leaf.keep_bits))))
            eff_bits[pstr] = eff
        else:
            n_fp += 1
            if id(leaf) in seen:
                aliased += 1
            else:
                seen.add(id(leaf))
                fp_bytes += np.size(leaf) * jnp.asarray(leaf).dtype.itemsize
    return {"quantized_leaves": n_q, "fp_leaves": n_fp,
            "quantized_bytes": q_bytes, "fp_bytes": fp_bytes,
            "metadata_bytes": meta_bytes, "aliased_leaves": aliased,
            "effective_bits": eff_bits}


class WireStoreReceiver:
    """Adapts a wire-fed :class:`~repro.transmission.client.ProgressiveClient`
    as a server's parameter source, so the *same* device-resident
    PlaneStore that the byte stream fills is the one the server decodes
    from — no second ingest, no second set of Pallas launches.

    ``materialize`` reads only *completed* stages: it goes straight to
    the store without flushing the client's pending partial-stage
    planes, so the served params are exactly the stage prefix
    (bit-identical to ``transmit_reconstruct`` at that stage) —
    mid-stage planes land with their stage's completion flush.
    """

    def __init__(self, client, prog: ProgressiveModel):
        self.client = client
        self.prog = prog

    @property
    def stages_complete(self) -> int:
        return self.client.stages_complete

    @property
    def store(self):
        return self.client.store

    def transport_health(self) -> dict:
        """Fault-tolerance counters of the underlying client (inert
        zeros on a trusted v1/v2 stream). ``stages_complete`` counts
        only *verified* checkpoints, so while a damaged unit is being
        re-fetched the engine keeps serving at the last verified stage
        — this surface is how operators see that happening."""
        c = self.client
        return {
            "integrity": bool(getattr(c, "integrity", False)),
            "stages_complete": c.stages_complete,
            "verified_units": getattr(c, "verified_units", 0),
            "pending_nacks": len(getattr(c, "nacks", {})),
            "quarantined": len(getattr(c, "quarantine_log", [])),
            "duplicate_units": getattr(c, "duplicate_units", 0),
            "resume_cursor": list(getattr(c, "resume_cursor", (0, 0))),
        }

    def materialize(self):
        if self.client.store is None:
            raise RuntimeError("wire header not received yet")
        leaves = self.client.store.materialize_leaves()
        return rebuild_params(self.prog, leaves, key_fn=wire.path_str)

    def materialize_resident(self, eligible=quantized_resident_eligible,
                             *, bits=None):
        """Quantized-resident view over the client's store: weight
        leaves stay QuantizedTensor accumulator views; this is the
        'metadata refresh' of an upgrade — no ``materialize()`` at
        all for the weights. ``bits=b`` yields the truncated-precision
        draft view (same accumulators, zero extra weight bytes)."""
        if self.client.store is None:
            raise RuntimeError("wire header not received yet")
        leaves = self.client.store.quantized_leaves(eligible=eligible,
                                                    bits=bits)
        return rebuild_params(self.prog, leaves, key_fn=wire.path_str)


class PrecisionManagedEngine:
    """Shared precision machinery: plane accumulators (own ReceiverState
    or an external receiver's store), residency-aware param refresh, and
    the jit'd prefill/decode entry points. Both the single-stream
    server and the slot pool extend this."""

    def __init__(self, model: Model, prog: ProgressiveModel, max_len: int,
                 receiver: WireStoreReceiver | None = None,
                 resident: str = "fp", mesh=None):
        if resident not in RESIDENT_MODES:
            raise ValueError(
                f"resident must be one of {RESIDENT_MODES}, got {resident!r}")
        self.model = model
        self.prog = prog
        self.max_len = max_len
        self.resident = resident
        self.mesh = mesh
        self._receiver = receiver
        self.state = (None if receiver is not None
                      else ReceiverState.init(prog, mesh=mesh))
        self._consumed = 0  # receiver mode: stages reflected in params
        self.params = None  # live param pytree at current precision
        self._prefill = jax.jit(self._meshed(model.prefill))
        # the steps donate the cache (argument 1): the buffer that comes
        # in is the one that goes out, with only the new rows written
        self._decode = jax.jit(self._meshed(model.decode_step),
                               donate_argnums=1)
        self._kv_path = kv_path(model.cfg)

    def _meshed(self, fn):
        """Wrap a model entry point so its *trace* runs under
        ``models.common.serving_mesh(self.mesh)``: every dispatch-helper
        output gets a replicated sharding constraint, which keeps all
        GSPMD-inserted collectives pure gathers (bit-exact — no sharded
        contractions, no partial-sum all-reduces; see
        ``launch.sharding.serving_spec_for_param``). Identity when the
        engine is single-device. The wrapper closes over the mesh value,
        not ``self``, so jit caching is unaffected."""
        if self.mesh is None:
            return fn
        mesh = self.mesh
        from repro.models.common import serving_mesh

        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with serving_mesh(mesh):
                return fn(*args, **kwargs)
        return wrapped

    # -- precision management ------------------------------------------------
    @property
    def stage(self) -> int:
        if self._receiver is not None:
            return self._consumed
        return self.state.received_stages

    @property
    def stages_available(self) -> int:
        """Stages the server could upgrade to right now."""
        if self._receiver is not None:
            return self._receiver.stages_complete
        return self.prog.n_stages

    def _count_kv_step(self, mode: str) -> None:
        """One dispatched decode, chunk or verify step, by how the
        model's caches move through it (``models.transformer.kv_path``)."""
        if _obs.enabled():
            _obs.get_registry().counter(
                "engine_kv_steps_total",
                "dispatched steps by KV cache path").inc(
                    mode=mode, path=self._kv_path)

    def decode_cache_size(self) -> int:
        """Compiled-executable count of the jitted decode step. The
        zero-recompile guarantee is exactly 'this stays 1 across every
        upgrade' — and for the slot pool, across every admission and
        eviction too."""
        return self._decode._cache_size()

    def _refresh_params(self) -> None:
        """Rebuild the live param pytree from the current accumulators
        at the current residency."""
        if self._receiver is not None:
            self.params = (self._receiver.materialize_resident()
                           if self.resident == "quantized"
                           else self._receiver.materialize())
        else:
            self.params = (self.state.materialize_resident(
                quantized_resident_eligible)
                if self.resident == "quantized"
                else self.state.materialize())

    def resident_report(self) -> dict:
        """Leaf-type audit of the *live* params (see
        :func:`resident_report`)."""
        if self.params is None:
            raise RuntimeError("no planes received yet")
        return resident_report(self.params)

    def receive_stage(self) -> None:
        """Pull the next stage's planes (server-push in a real
        deployment; here the planes live in ``self.prog``), or — in
        receiver mode — refresh params from the externally-fed store,
        catching up to every stage the receiver has completed.

        The OR is one batched ``plane_or_segments`` launch over the
        store's flat buffer. With ``resident="fp"`` the refresh is the
        store's incremental eq.-(5) materialize (only dirty tensors
        re-dequantize); with ``resident="quantized"`` it is a metadata
        refresh — new accumulator views + new traced scale/offset
        values, no weight dequantization anywhere."""
        t0 = time.perf_counter()
        if self._receiver is not None:
            avail = self._receiver.stages_complete
            if avail <= self._consumed:
                raise RuntimeError(
                    f"receiver has no new stage (at {avail}, "
                    f"served {self._consumed})")
            self._consumed = avail     # ingest happened externally
        else:
            s = self.state.received_stages + 1
            self.state = self.state.receive(self.prog.stage(s))
        t1 = time.perf_counter()
        with _obs.get_tracer().span("upgrade_refresh", stage=self.stage):
            self._refresh_params()
        # enqueue-time split consumed by upgrade_if_available's log
        self._last_upgrade_split = {
            "ingest_s": t1 - t0,
            "refresh_s": time.perf_counter() - t1,
        }


class ProgressiveServer(PrecisionManagedEngine):
    """Single lock-stepped request stream over device-resident plane
    accumulators + one jit'd decode step.

    Two feeding modes:

    * pull (default): ``receive_stage()`` ingests the next stage's
      planes from ``self.prog`` into the server's own ReceiverState
      (server-push in a real deployment).
    * receiver: constructed with ``receiver=`` (e.g.
      :class:`WireStoreReceiver` over the wire client's store) the
      server holds no accumulators of its own — ``receive_stage()``
      refreshes params from the externally-fed store. This is what the
      co-simulation :class:`~repro.transmission.session.Session` uses:
      bytes are ingested once, by the client.

    And two residency modes (``resident="fp" | "quantized"``), see the
    module docstring. Both serve the identical token stream — pinned by
    tests — but quantized residency allocates no fp weight buffers and
    upgrades without touching eq. (5) for the weights.
    """

    def __init__(self, model: Model, prog: ProgressiveModel, max_len: int,
                 receiver: WireStoreReceiver | None = None,
                 resident: str = "fp", mesh=None):
        super().__init__(model, prog, max_len, receiver=receiver,
                         resident=resident, mesh=mesh)
        self.caches = None
        self.pos = 0

    # -- serving ---------------------------------------------------------------
    def start(self, batch: dict) -> None:
        if self.params is None:
            raise RuntimeError("no planes received yet — call receive_stage()")
        last_logits, caches = self._prefill(self.params, batch)
        self.caches = self.model.grow_caches(caches, self.max_len)
        self.pos = batch["tokens"].shape[1]
        self.last_logits = last_logits

    def decode(self, steps: int, *,
               stage_arrival: Callable[[int], bool] | None = None,
               sync: bool = False,
               dispatch_window: int = 8) -> GenerationResult:
        """Greedy-decode ``steps`` tokens; before each step, consult
        ``stage_arrival(step)`` — True means the next plane landed and we
        upgrade in place (KV cache untouched; checking is host-side
        bookkeeping, so it costs no device sync).

        Dispatch is asynchronous by default: greedy sampling chains on
        device and the host blocks only every ``dispatch_window`` steps,
        so ingest and token reads overlap decode. ``per_step_s`` is then
        *derived* (window wall time / steps in window); ``window_s``
        holds the honest measurements and ``ttft_s``/``tpot_s`` the
        serving-level latencies. ``sync=True`` restores the old
        block-per-token behavior and its per-token timings."""
        if sync:
            dispatch_window = 1
        toks = []
        stage_at, upgrades, per_step = [], [], []
        window_s: list[tuple[int, float]] = []
        logits = self.last_logits
        t_start = time.perf_counter()
        ttft = None
        win_t0 = t_start
        win_steps = 0
        for i in range(steps):
            if stage_arrival and self.stage < self.prog.n_stages and stage_arrival(i):
                self.receive_stage()
                upgrades.append((i, self.stage))
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
            logits, self.caches = self._decode(
                self.params, self.caches, nxt, jnp.int32(self.pos)
            )
            self._count_kv_step("decode")
            self.pos += 1
            toks.append(nxt[:, 0])
            stage_at.append(self.stage)
            win_steps += 1
            if win_steps >= dispatch_window or i == steps - 1:
                jax.block_until_ready(logits)
                now = time.perf_counter()
                if ttft is None:
                    ttft = now - t_start
                dt = now - win_t0
                window_s.append((win_steps, dt))
                per_step.extend([dt / win_steps] * win_steps)
                if _obs.enabled():
                    _obs.get_tracer().record(
                        "decode_window", wall_s=dt, engine="single")
                win_t0 = now
                win_steps = 0
        total = time.perf_counter() - t_start
        self.last_logits = logits
        if _obs.enabled():
            reg = _obs.get_registry()
            reg.histogram("engine_ttft_s",
                          "wall seconds to first token value").observe(
                              ttft or 0.0, engine="single")
            reg.counter("engine_tokens_total",
                        "tokens emitted by serving engines").inc(
                            steps, engine="single")
        return GenerationResult(
            tokens=jnp.stack(toks, axis=1),
            stage_at_step=stage_at,
            upgrades=upgrades,
            per_step_s=per_step,
            window_s=window_s,
            ttft_s=ttft or 0.0,
            tpot_s=total / max(steps, 1),
            mode="sync" if sync else "async",
        )


# ---------------------------------------------------------------------------
# Continuous batching: the slot pool
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PoolRequest:
    """One serving request: a prompt and a generation budget."""

    rid: int
    prompt: Any                  # (S,) int32 token ids
    max_new_tokens: int
    extras: dict = dataclasses.field(default_factory=dict)
    # per-request fixed-size side inputs (e.g. "vision_embeds",
    # (vision_tokens, d_vision)), each WITHOUT the leading batch dim.
    # Prompt-derived encoder inputs ("enc_input") are not poolable —
    # see SlotPoolEngine.__init__


@dataclasses.dataclass
class _Slot:
    rid: int | None = None       # None = free
    dispatched: int = 0          # decode steps issued for this request
    budget: int = 0

    @property
    def free(self) -> bool:
        return self.rid is None


@dataclasses.dataclass
class PoolStepStats:
    """Host-visible outcome of a flushed dispatch window. The upgrade
    fields are the per-window overlap accounting: ``upgrades`` precision
    upgrades were enqueued while this window's steps were in flight, and
    enqueueing them held the host for ``upgrade_enqueue_s`` — the wall
    clock the window actually lost to upgrades (the device-side OR +
    refresh overlaps dispatched decode work)."""

    steps: int
    wall_s: float
    tokens_emitted: int
    upgrades: int = 0
    upgrade_enqueue_s: float = 0.0
    prefill_ticks: int = 0  # chunked-prefill blocks advanced this window


_RECURRENT_KINDS = ("mamba2", "mlstm", "slstm")
_CROSS_KINDS = ("cross", "selfcross")
_WINDOW_KINDS = ("swa", "swa_moe")


class SlotPoolEngine(PrecisionManagedEngine):
    """Continuous-batching progressive serving.

    A fixed pool of ``n_slots`` decode slots shares ONE cache pytree in
    the flash kernel's native ``(B, Kh, S, hd)`` layout, one live param
    pytree over the PlaneStore accumulators, and one compiled ragged
    ``decode_step`` (per-slot ``(B,)`` positions). Eviction just frees
    the host-side slot record. Neither admission nor eviction touches
    the decode executable.

    Admission is **chunked** by default (``chunked_prefill``): the
    prompt is staged host-side and consumed ``prefill_chunk`` tokens at
    a time by a batched ragged ``prefill_chunk`` launch that writes
    prompt KV straight into the slot's pooled cache rows — no batch-1
    prefill, no ``grow_caches``, no cache-sized copy on the admit path,
    and ONE compiled executable per chunk shape no matter how many
    distinct prompt lengths arrive (a flash crowd of novel lengths used
    to pay one prefill compile each). Chunk steps interleave with
    decode steps inside the dispatch window, so multiple queued
    requests make admission progress per window while resident slots
    keep decoding; a mid-prefill slot's device ``pos`` stays -1, which
    masks it out of every interleaved decode step (KV writes and
    recurrent-state updates included). Cross-attention archs (whose
    admission must run the vision/enc encoder) fall back to the legacy
    batch-1 path, with prompt lengths padded to power-of-two buckets
    (``prefill_buckets``) where masked positions are supported, so the
    prefill executable count is O(log max_len), not O(distinct
    lengths).

    Decode is dispatched in bounded asynchronous windows: within a
    window, greedy sampling chains device-side with no host sync;
    between windows the host reads token values, completes/evicts
    finished requests, admits queued ones, and applies precision
    upgrades — "batch-step granularity", zero recompiles (the PR-3
    traced ``received_bits`` invariant holds: nothing static changes).
    Upgrades are **zero-stall** by default (``double_buffer``): the
    PlaneStore ingest never donates its accumulators, so the OR +
    eq.-(5) refresh builds NEW buffers while in-flight steps read the
    old ones; ``upgrade_if_available`` just enqueues that work and the
    next dispatched step picks up the refreshed params in program
    order — no ``block_until_ready`` fence anywhere in the serving
    loop. Per-window overlap accounting lands in
    :class:`PoolStepStats`.

    Tokens emitted by a free slot are discarded on host; the kernel
    masks a free slot's whole cache row (``q_pos = -1``), so it costs
    one lane of the batched launch and never NaNs.
    """

    def __init__(self, model: Model, prog: ProgressiveModel, *,
                 n_slots: int, max_len: int,
                 receiver: WireStoreReceiver | None = None,
                 resident: str = "fp",
                 dispatch_window: int = 8,
                 eos_id: int | None = None,
                 ring_margin: int = 0,
                 chunked_prefill: bool | None = None,
                 prefill_chunk: int = 8,
                 prefill_buckets: bool = True,
                 double_buffer: bool = True,
                 mesh=None):
        super().__init__(model, prog, max_len, receiver=receiver,
                         resident=resident, mesh=mesh)
        if n_slots < 1:
            raise ValueError("n_slots must be >= 1")
        if model.cfg.enc_layers:
            # audio enc-dec: the cross-cache length is prompt-derived
            # (enc frames = seq // divisor), so per-request caches don't
            # tile into one fixed pool cache without per-slot memory
            # masking — single-stream serving still covers these archs
            raise NotImplementedError(
                "SlotPoolEngine does not support encoder-decoder models "
                "with prompt-derived encoder lengths (cfg.enc_layers > 0); "
                "use ProgressiveServer")
        kinds = set(model.cfg.cycle) | set(model.cfg.tail)
        chunk_ok = not (kinds & set(_CROSS_KINDS))
        if chunked_prefill is None:
            chunked_prefill = chunk_ok
        elif chunked_prefill and not chunk_ok:
            raise NotImplementedError(
                "chunked prefill is not supported for cross-attention "
                "archs (admission must run the vision/enc encoder); use "
                "chunked_prefill=None to fall back automatically")
        self.chunked_prefill = bool(chunked_prefill)
        self.prefill_chunk = max(1, int(prefill_chunk))
        if self.chunked_prefill and model.cfg.window and \
                (kinds & set(_WINDOW_KINDS)):
            # a chunk writes prefill_chunk positions ahead of the oldest
            # live window entry — same over-allocation argument as
            # speculative verify blocks (attention.py ring check)
            ring_margin = max(ring_margin, self.prefill_chunk)
        self._ring_margin = ring_margin
        # masked-position padding is only sound for plain attention: a
        # sliding-window ring has no masked slots and a recurrent state
        # would consume the padding tokens
        self.prefill_buckets = bool(prefill_buckets) and not \
            (kinds & (set(_WINDOW_KINDS) | set(_RECURRENT_KINDS)))
        self.double_buffer = bool(double_buffer)
        self.n_slots = n_slots
        self.dispatch_window = max(1, dispatch_window)
        # ring_margin over-allocates sliding-window ring caches for
        # speculative verify blocks and prefill chunks
        self.caches = model.init_caches(n_slots, max_len,
                                        ring_margin=ring_margin)
        self.pos = jnp.full((n_slots,), -1, jnp.int32)
        self.last_logits = jnp.full((n_slots, model.cfg.vocab), 0.0,
                                    jnp.float32)
        self.slots = [_Slot() for _ in range(n_slots)]
        self.queue: list[PoolRequest] = []       # FIFO admission backlog
        self.outputs: dict[int, list[int]] = {}  # rid -> generated tokens
        self.stage_log: dict[int, list[int]] = {}  # rid -> stage per token
        self.admit_stage: dict[int, int] = {}      # rid -> prefill stage
        self.admitted_order: list[int] = []        # rids, actual admission
        self.completed: set[int] = set()
        self._retired: set[int] = set()  # evicted, final window not yet flushed
        # in-flight dispatched steps awaiting a flush:
        # (tokens (B,1) device array, {slot: rid} snapshot, stage)
        self._pending: list[tuple[Any, dict[int, int], int]] = []
        self._win_t0: float | None = None
        self.window_stats: list[PoolStepStats] = []
        self.upgrade_stall_s: float = 0.0    # host time blocked on upgrades
        self.upgrade_enqueue_s: float = 0.0  # host time enqueueing them
        self.upgrade_log: list[dict] = []    # per-upgrade overlap record
        self.upgrades: list[tuple[int, int]] = []  # (global step, stage)
        self._step_count = 0
        self._tick_count = 0  # chunked-prefill blocks consumed
        self._win_upgrades = 0
        self._win_upgrade_enqueue_s = 0.0
        self._win_prefill_ticks = 0
        # chunked admission: slot -> staged prompt + consumption offset;
        # slots here hold a request (not free) but are NOT decoding yet
        self._prefill_state: dict[int, dict] = {}
        self._chunk_step = jax.jit(self._meshed(_make_chunk_step(model)),
                                   donate_argnums=1)
        # device-side companions updated by the chunk step when a slot's
        # prefill completes: the argmax of its last prompt row (the
        # request's first greedy token) lands in _last_tok (consumed by
        # the speculative pool's draft chain) and _first_cap (read at
        # flush for deferred first-token emission)
        self._last_tok = jnp.zeros((n_slots, 1), jnp.int32)
        self._first_cap = jnp.zeros((n_slots,), jnp.int32)
        if mesh is not None:
            # the pooled state starts replicated on the mesh, as the
            # jitted steps return it: single-device inputs on the first
            # step would compile a second executable for every later one
            (self.caches, self.pos, self.last_logits, self._last_tok,
             self._first_cap) = jax.device_put(
                (self.caches, self.pos, self.last_logits, self._last_tok,
                 self._first_cap),
                jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()))
        self._recurrent_cycle_keys = [
            f"{j}_{kind}" for j, kind in enumerate(model.cfg.cycle)
            if kind in _RECURRENT_KINDS]
        self._recurrent_tail_keys = [
            f"{i}_{kind}" for i, kind in enumerate(model.cfg.tail)
            if kind in _RECURRENT_KINDS]
        specs = model.input_specs(batch=1, seq_len=2, mode="prefill")
        self._extra_specs = {k: tuple(s.shape[1:])
                             for k, s in specs.items() if k != "tokens"}
        self._submit_t: dict[int, float] = {}   # rid -> submit wall time
        self.ttft_s: dict[int, float] = {}      # rid -> first-token latency
        # eos termination is checked at flush boundaries: a request may
        # decode up to dispatch_window - 1 tokens past its eos (the
        # standard async continuous-batching tradeoff); those trailing
        # tokens are dropped from its output
        self.eos_id = eos_id

    # -- admission / eviction ----------------------------------------------
    def free_slots(self) -> list[int]:
        return [i for i, s in enumerate(self.slots) if s.free]

    def active_rids(self) -> dict[int, int]:
        """Slots actively DECODING: admitted, prefill complete. A slot
        mid-chunked-prefill holds a request (not free) but is excluded —
        it joins decode snapshots once its last prompt chunk lands."""
        return {i: s.rid for i, s in enumerate(self.slots)
                if not s.free and i not in self._prefill_state}

    def submit(self, request: PoolRequest) -> None:
        """Queue a request; it is admitted into the next free slot at
        the next admission point (immediately if one is free). A
        malformed request raises HERE — before any device work."""
        self._validate_request(request)
        self._submit_t[request.rid] = time.perf_counter()
        self.queue.append(request)
        self._admit_from_queue()

    def _validate_request(self, req: PoolRequest) -> None:
        """Host-side (numpy-level) validation: nothing is traced,
        transferred or launched before a request is known to be
        well-formed. In particular a (1, S) prompt is rejected outright
        rather than silently squeezing through batch-1 prefill, and a
        bad ``extras`` shape fails before the prefill launch."""
        if req.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        prompt = np.asarray(req.prompt)
        if prompt.ndim != 1:
            raise ValueError(
                f"PoolRequest.prompt must be one-dimensional (S,), got "
                f"shape {prompt.shape}")
        if prompt.shape[0] < 1:
            raise ValueError("PoolRequest.prompt must hold >= 1 token")
        if prompt.shape[0] + req.max_new_tokens > self.max_len:
            # write positions reach prompt_len + budget - 1; past max_len
            # the cache write would silently drop the rows
            raise ValueError(
                f"request needs {prompt.shape[0]} prompt + "
                f"{req.max_new_tokens} new tokens > max_len {self.max_len}")
        for k, v in req.extras.items():
            if k not in self._extra_specs:
                raise ValueError(
                    f"unknown extras key {k!r}; this arch accepts "
                    f"{sorted(self._extra_specs)}")
            got, want = tuple(np.shape(v)), self._extra_specs[k]
            if got != want:
                raise ValueError(
                    f"extras[{k!r}] must have per-request shape {want} "
                    f"(no batch dim), got {got}")

    def _admit_from_queue(self) -> None:
        while self.queue and (free := self.free_slots()):
            self._admit(free[0], self.queue.pop(0))

    def _admit(self, slot: int, req: PoolRequest) -> None:
        if self.params is None:
            raise RuntimeError("no planes received yet — call receive_stage()")
        prompt = np.asarray(req.prompt, np.int32)
        self.slots[slot] = _Slot(rid=req.rid, dispatched=0,
                                 budget=req.max_new_tokens)
        self.outputs.setdefault(req.rid, [])
        self.stage_log.setdefault(req.rid, [])
        self.admit_stage[req.rid] = self.stage
        self.admitted_order.append(req.rid)
        self._post_admit(slot, req, int(prompt.shape[0]))
        if self.chunked_prefill and not req.extras:
            self._begin_chunked_prefill(slot, req, prompt)
        else:
            self._admit_batch1(slot, req, prompt)

    def _post_admit(self, slot: int, req: PoolRequest,
                    prompt_len: int) -> None:
        """Subclass hook, called once per admission before the prompt
        is consumed (speculative pool: position ceiling bookkeeping)."""

    def _begin_chunked_prefill(self, slot: int, req: PoolRequest,
                               prompt: np.ndarray) -> None:
        """Chunked admission is host bookkeeping only: stage the prompt
        and let :meth:`_prefill_tick` consume it ``prefill_chunk``
        tokens per block, writing KV straight into the slot's pooled
        cache rows. No KV reset is needed — a prior occupant's stale
        rows are provably invisible (causal mask + decode overwrites
        position p before any query >= p exists; a ring assigns
        non-negative k_pos only to slots the new occupant has written).
        A RECURRENT state is cumulative rather than positional, so it
        IS zeroed here. The slot's device pos stays -1 until the last
        chunk lands, masking it out of interleaved decode steps."""
        self._reset_recurrent_slot(slot)
        self._prefill_state[slot] = {"prompt": prompt, "off": 0,
                                     "rid": req.rid,
                                     "len": int(prompt.shape[0])}

    def _admit_batch1(self, slot: int, req: PoolRequest,
                      prompt: np.ndarray) -> None:
        """Legacy admission: batch-1 prefill, grow to max_len, one
        per-leaf slot write. Kept for cross-attention archs (the
        vision/enc encoder runs here) and as the explicit
        ``chunked_prefill=False`` baseline. With ``prefill_buckets``
        the prompt is padded to a power-of-two bucket with masked
        positions, so this path compiles O(log max_len) prefill
        variants instead of one per distinct prompt length."""
        L = int(prompt.shape[0])
        tokens = jnp.asarray(prompt)[None, :]
        n_valid = None
        if self.prefill_buckets:
            bucket = min(max(1 << (L - 1).bit_length(), 1), self.max_len)
            if bucket > L:
                tokens = jnp.pad(tokens, ((0, 0), (0, bucket - L)))
            n_valid = jnp.asarray([L], jnp.int32)
        batch = {"tokens": tokens}
        for k, v in req.extras.items():
            batch[k] = jnp.asarray(v)[None]
        if n_valid is None:
            last_logits, caches = self._prefill(self.params, batch)
        else:
            last_logits, caches = self._prefill(self.params, batch, n_valid)
        caches = self._grow_admitted(caches, L)
        self.caches = _write_slot_tree(self.caches, caches, slot,
                                       self.n_slots)
        self.pos = self.pos.at[slot].set(L)
        self.last_logits = self.last_logits.at[slot].set(
            last_logits[0].astype(self.last_logits.dtype))
        self._post_admit_batch1(slot, req, last_logits, L)

    def _grow_admitted(self, caches, prompt_len: int):
        """Grow a batch-1 prefill's caches to pool shape (subclassed to
        repack sliding-window rings by the speculative margin)."""
        return self.model.grow_caches(caches, self.max_len)

    def _post_admit_batch1(self, slot: int, req: PoolRequest,
                           last_logits, prompt_len: int) -> None:
        """Subclass hook after a batch-1 admission's device writes
        (speculative pool: immediate first-token emission)."""

    def _reset_recurrent_slot(self, slot: int) -> None:
        """Zero one slot's recurrent-state rows (mamba2/mlstm/slstm
        caches are cumulative — unlike KV rows, a prior occupant's
        state would leak into the new request). Host-side .at[].set
        per recurrent block, nothing cache-sized is copied."""
        for key in self._recurrent_cycle_keys:
            # stacked over cycles: leaves are (R, B, ...)
            self.caches["cycles"][key] = jax.tree.map(
                lambda a: a.at[:, slot].set(jnp.zeros((), a.dtype)),
                self.caches["cycles"][key])
        for key in self._recurrent_tail_keys:
            self.caches["tail"][key] = jax.tree.map(
                lambda a: a.at[slot].set(jnp.zeros((), a.dtype)),
                self.caches["tail"][key])

    def _prefill_tick(self) -> None:
        """Advance every mid-prefill slot by one (B, chunk) block — a
        single batched ``prefill_chunk`` launch; free and decoding
        slots ride along fully masked (tok_pos = -1). When a slot's
        last prompt token is inside this block, the device side
        installs its end position, last-row logits and first greedy
        token, so the slot joins the next decode snapshot with no host
        sync."""
        if not self._prefill_state:
            return
        with _obs.get_tracer().span("engine_prefill_tick",
                                    rows=len(self._prefill_state)):
            C, B = self.prefill_chunk, self.n_slots
            toks = np.zeros((B, C), np.int32)
            tpos = np.full((B, C), -1, np.int32)
            frow = np.full((B,), -1, np.int32)
            done: list[int] = []
            for slot, st in self._prefill_state.items():
                off, L = st["off"], st["len"]
                if off == 0:
                    # the stage the prompt is actually consumed at — an
                    # upgrade may land between submit and the first chunk
                    # tick. (Chunks beyond the first are not re-recorded: a
                    # mid-prefill upgrade makes a single "prefill stage"
                    # ill-defined; parity tests pin the upgrade-free case.)
                    self.admit_stage[st["rid"]] = self.stage
                n = min(C, L - off)
                toks[slot, :n] = st["prompt"][off:off + n]
                tpos[slot, :n] = np.arange(off, off + n, dtype=np.int32)
                if off + n == L:
                    frow[slot] = n - 1
                    done.append(slot)
                st["off"] = off + n
            (self.caches, self.pos, self.last_logits, self._last_tok,
             self._first_cap) = self._chunk_step(
                self.params, self.caches, jnp.asarray(toks), jnp.asarray(tpos),
                jnp.asarray(frow), self.pos, self.last_logits, self._last_tok,
                self._first_cap)
            self._count_kv_step("prefill_chunk")
            self._tick_count += 1
            self._win_prefill_ticks += 1
            for slot in done:
                del self._prefill_state[slot]
                self._on_prefill_complete(slot)

    def _on_prefill_complete(self, slot: int) -> None:
        """Subclass hook when a slot's chunked prefill finishes
        (speculative pool: deferred first-token emission)."""

    def prefill_cache_size(self) -> int:
        """Compiled-executable count on the ADMISSION path — the
        admission analogue of :meth:`decode_cache_size`. Chunked mode:
        one per chunk shape (one, in practice). Batch-1 mode: one per
        prompt-length bucket (O(log max_len) with ``prefill_buckets``,
        one per distinct length without)."""
        if self.chunked_prefill:
            return self._chunk_step._cache_size()
        return self._prefill._cache_size()

    def _note_first_token(self, rid: int) -> None:
        t = self._submit_t.get(rid)
        if t is not None and rid not in self.ttft_s:
            self.ttft_s[rid] = time.perf_counter() - t
            if _obs.enabled():
                _obs.get_registry().histogram(
                    "engine_ttft_s",
                    "wall seconds to first token value").observe(
                        self.ttft_s[rid], engine=type(self).__name__)

    def _evict(self, slot: int) -> int:
        rid = self.slots[slot].rid
        self.slots[slot] = _Slot()
        self.pos = self.pos.at[slot].set(-1)
        self._retired.add(rid)  # completed once its last window flushes
        return rid

    # -- batched ragged decode ---------------------------------------------
    def step(self) -> dict[int, int]:
        """One scheduling tick: advance chunked prefills by one block
        (if any are staged), then dispatch ONE batched decode step for
        every decoding slot (free and mid-prefill slots ride along
        masked). Returns the ``{slot: rid}`` snapshot of who the decode
        step ran for — empty when nothing is decoding yet. No host sync
        happens here, for either half."""
        if self.params is None:
            raise RuntimeError("no planes received yet — call receive_stage()")
        if self._win_t0 is None:
            self._win_t0 = time.perf_counter()
        with _obs.get_tracer().span("engine_step"):
            self._prefill_tick()
            snapshot = self.active_rids()
            if not snapshot:
                return snapshot
            nxt = jnp.argmax(self.last_logits,
                             axis=-1).astype(jnp.int32)[:, None]
            logits, self.caches = self._decode(self.params, self.caches, nxt,
                                               self.pos)
            self._count_kv_step("decode")
            active = jnp.asarray(
                [i in snapshot for i in range(self.n_slots)], dtype=bool)
            self.pos = jnp.where(active, self.pos + 1, self.pos)
            self.last_logits = logits
            self._pending.append((nxt, snapshot, self.stage))
            self._step_count += 1
            # dispatch-time bookkeeping: budgets decrement without
            # reading token values, so length-complete slots free
            # immediately
            for slot in snapshot:
                s = self.slots[slot]
                s.dispatched += 1
                if s.dispatched >= s.budget:
                    self._evict(slot)
        return snapshot

    def flush(self) -> PoolStepStats | None:
        """Block on the in-flight window, distribute token values to
        their requests, complete eos/budget-finished ones."""
        if not self._pending:
            return None
        with _obs.get_tracer().span("engine_flush"):
            jax.block_until_ready(self.last_logits)
            toks = np.asarray(jnp.concatenate([t for t, _, _ in self._pending],
                                              axis=1))  # (B, n_pending)
            wall = time.perf_counter() - (self._win_t0 or time.perf_counter())
            emitted = 0
            eos_hit: set[int] = set()
            for j, (_, snapshot, stage) in enumerate(self._pending):
                for slot, rid in snapshot.items():
                    if rid in eos_hit:
                        continue
                    tok = int(toks[slot, j])
                    if not self.outputs[rid]:
                        self._note_first_token(rid)
                    self.outputs[rid].append(tok)
                    self.stage_log[rid].append(stage)
                    emitted += 1
                    if self.eos_id is not None and tok == self.eos_id:
                        eos_hit.add(rid)
                        # the slot may already be freed by budget bookkeeping
                        if not self.slots[slot].free and \
                                self.slots[slot].rid == rid:
                            self._evict(slot)
            # every retired request's final in-flight tokens just landed;
            # incremental, so a long-lived pool never rescans its history
            self.completed |= self._retired
            self._retired.clear()
            stats = PoolStepStats(
                steps=len(self._pending), wall_s=wall,
                tokens_emitted=emitted, upgrades=self._win_upgrades,
                upgrade_enqueue_s=self._win_upgrade_enqueue_s,
                prefill_ticks=self._win_prefill_ticks)
            return self._record_window(stats)

    def _record_window(self, stats: PoolStepStats) -> PoolStepStats:
        """Window chokepoint shared with the speculative pool: append
        to the legacy ``window_stats`` view, reset the per-window
        accumulators, mirror the stats into the telemetry registry."""
        self.window_stats.append(stats)
        self._pending.clear()
        self._win_t0 = None
        self._win_upgrades = 0
        self._win_upgrade_enqueue_s = 0.0
        self._win_prefill_ticks = 0
        if _obs.enabled():
            engine = type(self).__name__
            reg = _obs.get_registry()
            reg.counter("engine_tokens_total",
                        "tokens emitted by serving engines").inc(
                            stats.tokens_emitted, engine=engine)
            reg.counter("engine_prefill_ticks_total",
                        "chunked prefill ticks").inc(
                            stats.prefill_ticks, engine=engine)
            reg.histogram("engine_window_steps",
                          "decode steps per flushed window").observe(
                              stats.steps, engine=engine)
            _obs.get_tracer().record("decode_window", wall_s=stats.wall_s,
                                     engine=engine)
        return stats

    def upgrade_if_available(self) -> bool:
        """Apply newly-arrived precision: in receiver mode this catches
        up to every stage the externally-fed store has completed; in
        pull mode (no receiver) it advances ONE stage per call — the
        caller models the arrival cadence, exactly like
        ``ProgressiveServer.decode``'s ``stage_arrival``.

        With ``double_buffer=True`` (default) this only ENQUEUES the
        upgrade: ``plane_or_segments`` never donates the store's
        accumulators, so the OR + eq.-(5) refresh builds new buffers
        while in-flight decode steps keep reading the old ones —
        functional double buffering, no fence, and the next dispatched
        step consumes the refreshed params in device program order.
        The host cost is the enqueue time alone (``upgrade_enqueue_s``,
        also surfaced per window in :class:`PoolStepStats`).
        ``double_buffer=False`` restores the old
        ``block_until_ready`` fence for A/B stall measurement; either
        way ``upgrade_stall_s`` records the honest measured host-
        blocked time and ``upgrade_log`` the per-upgrade split."""
        if self.stage >= self.prog.n_stages or \
                self.stages_available <= self.stage:
            return False
        with _obs.get_tracer().span("engine_upgrade") as sp:
            t0 = time.perf_counter()
            self.receive_stage()
            enqueue_s = time.perf_counter() - t0
            if not self.double_buffer:
                jax.block_until_ready(jax.tree.leaves(self.params))
            stall_s = time.perf_counter() - t0
            if sp is not None:
                sp["stage"] = self.stage
        self.upgrade_enqueue_s += enqueue_s
        self.upgrade_stall_s += stall_s
        self._win_upgrades += 1
        self._win_upgrade_enqueue_s += enqueue_s
        split = getattr(self, "_last_upgrade_split", None) or {}
        self._record_upgrade({
            "step": self._step_count, "stage": self.stage,
            "enqueue_s": enqueue_s, "stall_s": stall_s,
            # enqueue split: host time ingesting planes (store OR
            # dispatch; ~0 in receiver mode where the wire client
            # ingested) vs refreshing the resident param views. The
            # fence component (stall - enqueue) is 0 with double_buffer.
            "ingest_s": split.get("ingest_s", 0.0),
            "refresh_s": split.get("refresh_s", 0.0),
            "fence_s": stall_s - enqueue_s,
            "sharded": self.mesh is not None,
            "double_buffer": self.double_buffer})
        self.upgrades.append((self._step_count, self.stage))
        return True

    def _record_upgrade(self, rec: dict) -> None:
        """Upgrade chokepoint: the legacy ``upgrade_log`` record plus
        registry counters/histograms over the same values."""
        self.upgrade_log.append(rec)
        if _obs.enabled():
            engine = type(self).__name__
            reg = _obs.get_registry()
            reg.counter("engine_upgrades_total",
                        "precision upgrades applied").inc(
                            engine=engine, stage=rec["stage"])
            reg.histogram("engine_upgrade_enqueue_s",
                          "host enqueue seconds per upgrade").observe(
                              rec["enqueue_s"], engine=engine)
            reg.histogram("engine_upgrade_stall_s",
                          "host-blocked seconds per upgrade").observe(
                              rec["stall_s"], engine=engine)

    def run(self, *, max_steps: int = 100_000,
            on_window: Callable[[int], None] | None = None) -> dict[int, list[int]]:
        """Drive the pool until every submitted request completes.
        ``on_window(step_count)`` runs at every window boundary (the
        session uses it to feed bytes / admit staggered arrivals /
        upgrade)."""
        while (any(not s.free for s in self.slots) or self.queue):
            for _ in range(self.dispatch_window):
                if not any(not s.free for s in self.slots):
                    break
                self.step()
                if self._step_count >= max_steps:
                    break
            self.flush()
            self._admit_from_queue()
            if on_window is not None:
                on_window(self._step_count)
            if self._step_count >= max_steps:
                break
        self.flush()
        return {rid: list(v) for rid, v in self.outputs.items()}


def _make_chunk_step(model: Model):
    """Build the jitted chunked-admission step: consume one (B, C)
    prompt block into the pooled caches and, for slots whose final
    prompt token is inside this block (``final_row[b] >= 0`` = its row
    index), install their decode handoff state device-side — end
    position, last-row logits, and the argmax first token (into both
    the last-token chain and the first-token capture buffer). Slots
    with ``final_row = -1`` (mid-prompt, decoding, free) pass their
    state through untouched. ONE executable per (B, C) shape serves
    every admission regardless of prompt length."""

    def chunk_step(params, caches, tokens, tok_pos, final_row, pos,
                   last_logits, last_tok, first_cap):
        logits, caches = model.prefill_chunk(params, caches, tokens,
                                             tok_pos)
        C = tokens.shape[1]
        row = jnp.clip(final_row, 0, C - 1)
        sel = jnp.take_along_axis(logits, row[:, None, None],
                                  axis=1)[:, 0]               # (B, V)
        done = final_row >= 0
        last_logits = jnp.where(done[:, None],
                                sel.astype(last_logits.dtype), last_logits)
        end = jnp.take_along_axis(tok_pos, row[:, None], axis=1)[:, 0] + 1
        pos = jnp.where(done, end, pos)
        first = jnp.argmax(sel, axis=-1).astype(jnp.int32)
        last_tok = jnp.where(done[:, None], first[:, None], last_tok)
        first_cap = jnp.where(done, first, first_cap)
        return caches, pos, last_logits, last_tok, first_cap

    return chunk_step


def _write_slot_tree(pool, one, slot: int, n_slots: int):
    """Write a batch-1 cache pytree into batch row ``slot`` of the pool
    cache pytree. The batch axis of each leaf is located structurally:
    it is the one axis where the pool leaf is ``n_slots`` wide and the
    single-request leaf is 1 (leaves with identical shapes — n_slots ==
    1 — are replaced outright)."""

    def write(p, o):
        if p.shape == o.shape:
            return o.astype(p.dtype)
        cand = [d for d, (a, b) in enumerate(zip(p.shape, o.shape))
                if a != b]
        if len(cand) != 1 or o.shape[cand[0]] != 1 or \
                p.shape[cand[0]] != n_slots:
            raise ValueError(
                f"cannot locate batch axis: pool {p.shape} vs one {o.shape}")
        start = [0] * p.ndim
        start[cand[0]] = slot
        return jax.lax.dynamic_update_slice(p, o.astype(p.dtype), start)

    return jax.tree.map(write, pool, one)
