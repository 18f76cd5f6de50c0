"""On-chip smoke test: olmo-1b at its published width, served end to end.

    python chip_smoke.py              # one TPU chip
    python chip_smoke.py --chips 4    # sharded pool on four chips

One chip. The server side (``init -> divide -> encode``) seeds olmo-1b
(16 layers, d_model 2048, vocab 50304) at random and divides it into
eight 2-bit planes; ``divide`` moves each plane to host memory as soon as
it is split, so the chip never holds params, codes and planes at once.
The wire stream then runs through ``Session.from_scenario(blob,
pod-coldstart)`` into ``ProgressiveClient -> PlaneStore -> SlotPoolEngine``
(``run_serving_pool``: quantized residency, chunked prefill, 4 slots,
4 prompts of 128 tokens, 16 new tokens each) and must reach stage 8.
Then every Pallas kernel is checked against its ``kernels/ref.py`` oracle
at olmo-1b shapes (the plane unpack also at every width it takes).

Four chips. The same stream is served twice in one process: by the
one-chip pool and by the pool on ``make_serving_mesh(4)``. Tokens must be
identical at every stage.

Earlier lines report phase timings, compile time, persistent-cache hits
and the chip's memory after each phase. Any failed check exits non-zero.
The last line of a run that passed every check is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``;
without a TPU the script exits non-zero before any work.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

SEED = 0
N_SLOTS, PROMPT_LEN, NEW_TOKENS = 4, 128, 16
# Mosaic kernels at their worst contract each product in one bf16 MXU
# pass (8-bit mantissa: relative rounding 2^-9 per operand) and write
# bf16 attention outputs (2^-9). Independent rounding errors add in
# quadrature, so the output's relative RMS error stays a small multiple
# of 2^-9; 2^-6 leaves 8x headroom, while a wrong mask, position or
# block index shows up as an O(1) error.
FLOAT_TOL = 2.0 ** -6


class Smoke:
    """Phase bookkeeping: timings, compile counters, chip memory, and
    the list of failed checks."""

    def __init__(self, dev):
        self.dev = dev
        self.failures: list[str] = []
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_requests = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/compile_requests_use_cache":
            self.cache_requests += 1

    def phase(self, name: str, fn, *args, **kw):
        c0, n0, h0, r0 = (self.compile_s, self.compiles, self.cache_hits,
                          self.cache_requests)
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        jax.block_until_ready(out)
        wall = time.perf_counter() - t0
        mem = self.dev.memory_stats() or {}
        gib = 2.0 ** 30
        print(f"[{name}] wall {wall:.1f}s; compile {self.compile_s - c0:.1f}s "
              f"over {self.compiles - n0} executables; persistent cache "
              f"{self.cache_hits - h0} hits of {self.cache_requests - r0} "
              f"lookups; chip memory in use "
              f"{mem.get('bytes_in_use', 0) / gib:.2f} GiB, peak "
              f"{mem.get('peak_bytes_in_use', 0) / gib:.2f} GiB of "
              f"{mem.get('bytes_limit', 0) / gib:.2f} GiB", flush=True)
        return out

    def check(self, name: str, ok: bool, detail: str) -> None:
        print(f"  {'ok  ' if ok else 'FAIL'} {name}: {detail}", flush=True)
        if not ok:
            self.failures.append(name)


def server_side(smoke: Smoke, cfg):
    """init -> divide -> encode: the paper's remote server, one phase
    each so the chip's peak is reported after every step. ``init`` runs
    as one executable, so its random-number temporaries never pile up
    op by op."""
    from repro.core import wire
    from repro.core.progressive import divide
    from repro.models.model import build_model

    model = build_model(cfg)
    params = smoke.phase("server side: init", jax.jit(model.init),
                         jax.random.PRNGKey(SEED))
    prog = smoke.phase("server side: divide", divide, params)
    del params
    blob = smoke.phase("server side: encode", wire.encode, prog)
    return model, prog, blob


def serve(model, prog, blob, mesh=None):
    """The main path: wire -> ProgressiveClient -> PlaneStore -> pool."""
    from repro.transmission import Session, get_scenario

    session = Session.from_scenario(blob, get_scenario("pod-coldstart"),
                                    seed=SEED)
    arrivals = session.stage_arrival_times()
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, model.cfg.vocab, PROMPT_LEN, dtype=np.int32)
               for _ in range(N_SLOTS)]
    # The pool takes ceil(PROMPT_LEN / 8) prefill rounds (8: its default
    # chunk) and NEW_TOKENS - 1 more to decode. Spreading the download
    # over 3/4 of them lands upgrades during both prefill and decode,
    # and the last stage before the end.
    rounds = -(-PROMPT_LEN // 8) + NEW_TOKENS - 1
    step_s = (arrivals[-1] - arrivals[0]) / (rounds * 3 // 4)
    return session.run_serving_pool(
        model, prog, prompts=prompts, max_new_tokens=NEW_TOKENS,
        n_slots=N_SLOTS, resident="quantized", chunked_prefill=True,
        step_time_s=step_s, mesh=mesh)


def check_serving(smoke: Smoke, res, prog, vocab: int) -> None:
    eng = res.server
    smoke.check("decode executables", eng.decode_cache_size() == 1,
                f"{eng.decode_cache_size()} (a compile count never "
                f"drops, so 1 now means 1 after every upgrade)")
    smoke.check("prefill-chunk executables", eng.prefill_cache_size() == 1,
                f"{eng.prefill_cache_size()}")
    stages = [s for _, s in res.upgrades]
    smoke.check("stage climbs to n_stages",
                eng.stage == prog.n_stages and stages == sorted(set(stages))
                and stages[-1] == prog.n_stages,
                f"upgrades (pool step, stage) {res.upgrades}")
    toks = np.asarray([res.tokens[r] for r in sorted(res.tokens)])
    smoke.check("tokens", toks.shape == (N_SLOTS, NEW_TOKENS)
                and bool(((toks >= 0) & (toks < vocab)).all()),
                f"shape {toks.shape}, range [{toks.min()}, {toks.max()}] "
                f"of vocab {vocab}")
    served = sorted({s for log in eng.stage_log.values() for s in log})
    smoke.check("tokens served at the final stage",
                served[-1] == prog.n_stages, f"stages seen {served}")
    logits = np.asarray(eng.last_logits)
    smoke.check("last logits finite", bool(np.isfinite(logits).all()),
                f"shape {logits.shape}")
    n = eng.n_slots
    i32 = jnp.int32
    dec = eng._decode.lower(eng.params, eng.caches, jnp.zeros((n, 1), i32),
                            eng.pos).compile().as_text()
    C = eng.prefill_chunk
    chunk = eng._chunk_step.lower(
        eng.params, eng.caches, jnp.zeros((n, C), i32),
        jnp.full((n, C), -1, i32), jnp.full((n,), -1, i32), eng.pos,
        eng.last_logits, eng._last_tok, eng._first_cap).compile().as_text()
    for name, txt in (("decode", dec), ("prefill-chunk", chunk)):
        k = txt.count("tpu_custom_call")
        smoke.check(f"Pallas kernels in the {name} executable", k > 0,
                    f"{k} tpu_custom_call sites")


def _rel_err(y, ref, rows=None) -> float:
    y = np.asarray(y, np.float64)
    ref = np.asarray(ref, np.float64)
    if rows is not None:
        y, ref = y[rows], ref[rows]
    return float(np.linalg.norm(y - ref) / np.linalg.norm(ref))


def check_kernels(smoke: Smoke, cfg, blob: bytes) -> None:
    """Each kernel against its kernels/ref.py oracle at olmo-1b shapes.
    The oracles run at 'highest' matmul precision: the plain f32
    reference. The kernels run as the serving path runs them."""
    from repro.core import wire
    from repro.core.plane_store import DEFAULT_BLOCK, PlaneStore
    from repro.core.quantize import dequant_affine
    from repro.kernels import ops, ref

    def oracle(fn, *args):
        with jax.default_matmul_precision("highest"):
            return fn(*args)

    key = iter(jax.random.split(jax.random.PRNGKey(SEED + 1), 32))
    d, hd, H = cfg.d_model, cfg.hd, cfg.n_heads
    B, S, T = N_SLOTS, 2048, 8
    for qdt, bits in ((jnp.uint8, 8), (jnp.uint16, 16)):
        scale, offset = dequant_affine(jnp.float32(-0.05), jnp.float32(0.05),
                                       bits)
        for M in (N_SLOTS, N_SLOTS * 8):
            for N in (cfg.d_ff, cfg.vocab):
                x = jax.random.normal(next(key), (M, d), jnp.bfloat16)
                q = jax.random.bits(next(key), (d, N), qdt)
                e = _rel_err(ops.dequant_matmul(x, q, scale, offset),
                             oracle(ref.dequant_matmul_ref, x, q, scale,
                                    offset))
                smoke.check(f"dequant_matmul {np.dtype(qdt).name} "
                            f"M={M} K={d} N={N}", e <= FLOAT_TOL,
                            f"relative error {e:.3e} <= {FLOAT_TOL:.3e}")

    k = jax.random.normal(next(key), (B, H, S, hd), jnp.bfloat16)
    v = jax.random.normal(next(key), (B, H, S, hd), jnp.bfloat16)
    k_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    q1 = jax.random.normal(next(key), (B, H, hd), jnp.bfloat16)
    q_pos = jnp.asarray([5, 700, S - 1, -1], jnp.int32)  # -1: a free slot
    e = _rel_err(ops.flash_decode(q1, k, v, k_pos, q_pos),
                 oracle(ref.flash_decode_ref, q1, k, v, k_pos, q_pos),
                 np.asarray(q_pos) >= 0)
    smoke.check(f"flash_decode B={B} H={H} S={S} hd={hd}", e <= FLOAT_TOL,
                f"relative error {e:.3e} over live slots")
    qT = jax.random.normal(next(key), (B, T, H, hd), jnp.bfloat16)
    base = np.asarray([0, 300, S - T, -1])
    rows = np.where(base[:, None] >= 0, base[:, None] + np.arange(T), -1)
    rows[1, 5:] = -1                          # ragged: a short last chunk
    tpos = jnp.asarray(rows, jnp.int32)
    for name, kern, ora in (
            ("flash_verify", ops.flash_verify, ref.flash_verify_ref),
            ("prefill_attention", ops.prefill_attention,
             ref.flash_prefill_ref)):
        e = _rel_err(kern(qT, k, v, k_pos, tpos),
                     oracle(ora, qT, k, v, k_pos, tpos), rows >= 0)
        smoke.check(f"{name} B={B} T={T} H={H} S={S} hd={hd}",
                    e <= FLOAT_TOL, f"relative error {e:.3e} over live rows")
    del k, v

    # plane OR over the whole olmo-1b uint16 buffer, with the segment
    # table of the real store layout and a different shift per tensor
    store = PlaneStore.from_wire_meta(wire.decode_header(blob)[0])
    n = max(t.offset + t.padded for t in store.slots)
    starts = jnp.asarray([t.offset // DEFAULT_BLOCK for t in store.slots],
                         jnp.int32)
    del store
    shifts = jnp.asarray(np.random.default_rng(SEED).choice(
        np.arange(0, 16, 2), starts.shape[0]), jnp.int32)
    acc = jax.jit(lambda k: jax.random.bits(k, (n,), jnp.uint16))(next(key))
    plane = jax.jit(lambda k: jax.random.bits(k, (n,), jnp.uint16)
                    & jnp.uint16(3))(next(key))
    out = ops.plane_or_segments(acc, plane, starts, shifts,
                                block=DEFAULT_BLOCK)
    rows_n = n // DEFAULT_BLOCK
    chunk = next(rows_n // c for c in range(1, rows_n + 1)
                 if rows_n % c == 0 and rows_n // c <= 1 << 16)

    @jax.jit
    def equal_to_oracle(out, acc, plane):
        # chunked, so the oracle never materializes a second buffer
        blk = jnp.arange(rows_n, dtype=jnp.int32)
        sh = shifts[jnp.searchsorted(starts, blk, side="right") - 1]

        def body(i, ok):
            def rows_of(a):
                return jax.lax.dynamic_slice_in_dim(
                    a.reshape(rows_n, -1), i * chunk, chunk)
            want = ref.plane_or_ref(rows_of(acc), rows_of(plane),
                                    rows_of(sh[:, None]))
            return ok & jnp.array_equal(rows_of(out), want)

        return jax.lax.fori_loop(0, rows_n // chunk, body, jnp.bool_(True))

    same = bool(equal_to_oracle(out, acc, plane))
    smoke.check(f"plane_or_segments n={n} segments={starts.shape[0]}", same,
                "bit-identical to the oracle" if same else "differs")
    del out, acc, plane

    # plane unpack: every width into uint16 on a small buffer, and the
    # 2-bit stage of the whole olmo-1b buffer as the store uploads it
    for width, m in ((1, 1 << 20), (2, 1 << 20), (4, 1 << 20), (8, 1 << 20),
                     (2, n)):
        nbytes = m * width // 8
        packed = jax.jit(lambda k: jax.random.bits(k, (nbytes,), jnp.uint8)
                         )(next(key))
        out = ops.plane_unpack(packed, width=width, dtype=jnp.uint16)
        chunk = next(nbytes // c for c in range(1, nbytes + 1)
                     if nbytes % c == 0 and nbytes // c <= 1 << 16)

        @jax.jit
        def unpack_equal(out, packed):
            # chunked: the oracle's (bytes, 8 / width) array pads to 128
            # lanes on the chip
            vals = chunk * 8 // width

            def body(i, ok):
                want = ref.plane_unpack_ref(jax.lax.dynamic_slice_in_dim(
                    packed, i * chunk, chunk), width, jnp.uint16)
                got = jax.lax.dynamic_slice_in_dim(out, i * vals, vals)
                return ok & jnp.array_equal(got, want)

            return jax.lax.fori_loop(0, nbytes // chunk, body,
                                     jnp.bool_(True))

        same = bool(unpack_equal(out, packed))
        smoke.check(f"plane_unpack width={width} n={m} -> uint16", same,
                    "bit-identical to the oracle" if same else "differs")
        del out, packed


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded pool and the one-chip "
                         "pool it must match token for token")
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX's first device is {dev.platform!r}, "
                 f"not a TPU; nothing was run")
    n_dev = len(jax.devices())
    if n_dev < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs that many TPU "
                 f"devices, JAX sees {n_dev}")

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    print(f"device: {dev.platform} {dev.device_kind} x{n_dev}; "
          f"compile cache at {enable_compile_cache()}", flush=True)
    smoke = Smoke(dev)
    cfg = get_config("olmo-1b")
    model, prog, blob = server_side(smoke, cfg)
    print(f"  olmo-1b: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"vocab {cfg.vocab}; {len(prog.tensors)} tensors, "
          f"{prog.n_stages} stages, {len(blob)} wire bytes", flush=True)

    if args.chips == 1:
        res = smoke.phase("serve: pod-coldstart, quantized, chunked prefill",
                          serve, model, prog, blob)
        check_serving(smoke, res, prog, cfg.vocab)
        del res
        smoke.phase("kernels vs oracles", check_kernels, smoke, cfg, blob)
    else:
        from repro.launch.mesh import make_serving_mesh

        one = smoke.phase("serve on one chip", serve, model, prog, blob)
        one_tokens, one_stages = dict(one.tokens), dict(one.server.stage_log)
        check_serving(smoke, one, prog, cfg.vocab)
        del one
        mesh = make_serving_mesh(args.chips)
        four = smoke.phase(f"serve on {args.chips} chips", serve, model,
                           prog, blob, mesh)
        check_serving(smoke, four, prog, cfg.vocab)
        smoke.check("sharded tokens identical to one chip",
                    four.tokens == one_tokens, f"{len(one_tokens)} requests")
        smoke.check("same stage at every token",
                    dict(four.server.stage_log) == one_stages,
                    f"stages {sorted({s for v in one_stages.values() for s in v})}")

    if smoke.failures:
        sys.exit(f"chip_smoke: {len(smoke.failures)} check(s) failed: "
                 f"{', '.join(smoke.failures)}")
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": n_dev}}))


if __name__ == "__main__":
    main()
