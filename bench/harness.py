"""One run of one cell: set-up, the measured window, the check, the result.

The program is driven only through its public entry points:
``wire.encode``, ``ProgressiveClient.feed``, ``WireStoreReceiver``,
``SlotPoolEngine`` (with the program's own defaults for prefill chunk,
dispatch window and double buffering) and its ``submit``, ``run(on_window=)``,
``upgrade_if_available`` and ``outputs``. The benchmark owns the wall
clock: a thread of its own hands the wire to ``feed`` as the mix's link
delivers it, and the serving thread submits each request when it is due;
the program owns what happens inside those calls.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import logging
import sys
import tempfile
import threading
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent          # bench/
REPO = ROOT.parent
if str(REPO / "src") not in sys.path:
    sys.path.insert(0, str(REPO / "src"))

from bench import arch, load_file  # noqa: E402
from bench import traffic as traffic_mod  # noqa: E402

MIB = 1 << 20
SETUP_FEED_BYTES = 64 * MIB


class WindowClosed(Exception):
    """Raised from the window callback to end the measured window."""


# -- loading the cell ----------------------------------------------------------

def load_benchmark(root: Path = REPO) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_spec(bench: dict, workload: str, root: Path = REPO) -> dict:
    """Everything one cell needs, found by the names in BENCHMARK.json."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    confs = {c["name"]: c for c in bench["configs"]}
    cfg = json.loads((root / confs[w["config"]]["file"]).read_text())
    mix = traffic_mod.load_mix(w["traffic"], ROOT)
    limits = json.loads((ROOT / "limits" / f"{workload}.json").read_text())
    return {"workload": w, "cfg": cfg, "mix": mix, "limits": limits}


def metric_names(bench: dict, workload: str, trace: bool) -> list[dict]:
    """The metrics a run of this cell reports: its end-to-end metrics
    (trace off) or its per-layer metrics (trace on)."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


def load_reader(name: str):
    return load_file(ROOT / "metrics" / f"{name}.py", "bench_metric_").read


def load_peaks(kind: str) -> dict:
    table = json.loads((ROOT / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise SystemExit(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


# -- the program, as the configuration states it --------------------------------

def arch_config(cfg: dict):
    """The program's ArchConfig for this configuration file."""
    import jax.numpy as jnp
    from repro.configs import get_config

    base = get_config(cfg["program_config"])
    fields = dict(arch.load(cfg).arch_fields(cfg))
    fields["dtype"] = getattr(jnp, cfg["dtype"])
    fields["param_dtype"] = getattr(jnp, cfg["param_dtype"])
    return dataclasses.replace(base, **fields)


def policy(cfg: dict):
    from repro.core.bitplanes import PlaneSchedule
    from repro.core.policy import UniformPolicy

    return UniformPolicy(PlaneSchedule(bits=cfg["bits"],
                                       widths=tuple(cfg["plane_widths"])))


# -- compile accounting ----------------------------------------------------------

class Compiles:
    """Counts compiles and persistent-cache hits, sums the seconds JAX
    spends tracing, lowering and compiling (or loading) by stage, and
    names what compiled (from JAX's compile log) while naming is on. JAX
    times a load from the persistent cache as a compile too, so fresh
    compiles are ``n - hits``."""

    STAGES = {"/jax/core/compile/jaxpr_trace_duration": "trace",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
              "/jax/core/compile/backend_compile_duration": "compile"}

    def __init__(self):
        import jax

        self.n = 0
        self.hits = 0
        self.secs = dict.fromkeys(self.STAGES.values(), 0.0)
        self.names: list[str] = []
        self.naming = False
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)
        self._handler = logging.Handler()
        self._handler.emit = self._log
        logging.getLogger("jax").addHandler(self._handler)

    def _dur(self, event, secs, **_):
        stage = self.STAGES.get(event)
        if stage is not None:
            self.secs[stage] += secs
            self.n += stage == "compile"

    def _ev(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _log(self, record):
        if self.naming:
            msg = record.getMessage()
            if msg.startswith("Compiling "):
                self.names.append(msg[10:60])

    def start_naming(self):
        """Name what compiles from here on, without printing JAX's log."""
        import jax

        self.naming = True
        lg = logging.getLogger("jax")
        self._quiet = [(h, h.level) for h in lg.handlers if h is not self._handler]
        for h, _ in self._quiet:
            h.setLevel(logging.CRITICAL)
        self._prop = lg.propagate
        lg.propagate = False
        jax.config.update("jax_log_compiles", True)

    def stop_naming(self):
        import jax

        jax.config.update("jax_log_compiles", False)
        logging.getLogger("jax").propagate = self._prop
        for h, level in self._quiet:
            h.setLevel(level)
        self.naming = False

    def mark(self):
        return self.n, self.hits, dict(self.secs)


# -- the window ------------------------------------------------------------------

@dataclasses.dataclass
class Record:
    """What the host saw during the window, on the window's clock
    (seconds since it opened)."""

    requests: dict = dataclasses.field(default_factory=dict)
    feeds: list = dataclasses.field(default_factory=list)       # (t0, t1, bytes)
    upgrades: list = dataclasses.field(default_factory=list)    # (t, stage)
    checksums: list = dataclasses.field(default_factory=list)   # (stage, device array)
    window_s: float = 0.0
    ready_s: float | None = None     # when the engine first had weights
    span: tuple | None = None        # (start, stop) of the traced part


class Feeder(threading.Thread):
    """The receiving end of the link, a thread of the benchmark's own: it
    hands the client every whole chunk that has arrived by the wall clock,
    one ``feed`` call per chunk, as soon as the call before returns. The
    serving thread holds ``lock`` while it applies an upgrade, and each
    ``feed`` call runs under it, so an upgrade never reads the store
    halfway through a stage's OR. ``landed`` is set when a stage
    completes."""

    def __init__(self, client, blob: memoryview, link, rec: Record, now, lock):
        super().__init__(name="bench-feeder", daemon=True)
        self.client, self.blob, self.link, self.rec, self.now = client, blob, link, rec, now
        self.lock = lock
        self.stop = threading.Event()
        self.landed = threading.Event()
        self.error: BaseException | None = None

    def run(self) -> None:
        from jax.profiler import TraceAnnotation

        link, stages = self.link, 0
        try:
            while not self.stop.is_set():
                t = self.now()
                n = link.available(t)
                if n == 0:
                    nxt = link.next_s()
                    if nxt is None:
                        return
                    self.stop.wait(max(0.0, nxt - t))
                    continue
                k = min(link.chunk, n)
                with self.lock, TraceAnnotation("bench:feed"):
                    t0 = self.now()
                    self.client.feed(self.blob[link.taken:link.taken + k])
                    t1 = self.now()
                self.rec.feeds.append((t0, t1, k))
                link.take(k)
                if self.client.stages_complete > stages:
                    stages = self.client.stages_complete
                    self.landed.set()
        except BaseException as e:  # handed to the serving thread
            self.error = e
            self.landed.set()


class Window:
    """Drives one engine through one window by the wall clock: requests
    are submitted when due at the engine's window boundaries, and, where
    the mix streams the wire, a ``Feeder`` thread feeds the client
    meanwhile."""

    def __init__(self, engine, client, mix: dict, reqs: list, seconds: float,
                 blob=None, profiler=None, checksum=None, rid_base: int = 0):
        self.engine = engine
        self.client = client
        self.mix = mix
        self.reqs = reqs
        self.seconds = seconds
        self.profiler = profiler   # (start, stop) callables, or None
        self.tracing = False
        self.checksum = checksum
        self.rid_base = rid_base
        self.rec = Record()
        self.lock = threading.Lock()
        stream = mix.get("stream")
        self.feeder = (Feeder(client, memoryview(blob),
                              traffic_mod.Link(stream, len(blob)), self.rec,
                              self.now, self.lock)
                       if stream is not None else None)
        self.next_req = 0
        self.live: set[int] = set()
        self.t0 = 0.0

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def run(self) -> Record:
        from jax.profiler import TraceAnnotation

        self.t0 = time.perf_counter()
        if self.feeder is not None:
            self.feeder.start()
        try:
            with TraceAnnotation("bench:window"):
                while True:
                    self.pump()
                    if self._busy():
                        with TraceAnnotation("bench:serve"):
                            self.engine.run(on_window=lambda _steps: self.pump())
                    else:
                        with TraceAnnotation("bench:idle"):
                            self._idle()
        except WindowClosed:
            self.rec.window_s = self.now()
        finally:
            if self.feeder is not None:
                self.feeder.stop.set()
                self.feeder.join()
        if self.tracing:
            self._stop_trace()
        return self.rec

    def _busy(self) -> bool:
        e = self.engine
        return bool(e.queue) or len(e.free_slots()) < e.n_slots

    def _idle(self) -> None:
        """Nothing to serve: wait until the next request is due, a stage
        lands or the traced part starts or ends."""
        t = self.now()
        nxt = [self.seconds]
        if self.next_req < len(self.reqs):
            nxt.append(self.reqs[self.next_req].due_s)
        if self.profiler is not None and self.rec.span is None:
            nxt.append(self.mix.get("trace_start_s", 0.0))
        if self.tracing and self._trace_deadline() is not None:
            nxt.append(self._trace_deadline())
        wait = min(nxt) - t
        if wait <= 0:
            return
        if self.feeder is not None:
            self.feeder.landed.wait(wait)
        else:
            time.sleep(wait)

    def _trace_deadline(self) -> float | None:
        """When the traced part ends: ``trace_seconds`` after the first
        upgrade (mixes with ``trace_after_first_upgrade``) or after it
        starts at ``trace_start_s``."""
        if self.profiler is None or self.rec.span is not None and len(self.rec.span) == 2:
            return None
        if self.mix.get("trace_after_first_upgrade"):
            if not self.rec.upgrades:
                return None
            return self.rec.upgrades[0][0] + self.mix["trace_seconds"]
        return self.mix.get("trace_start_s", 0.0) + self.mix["trace_seconds"]

    def _start_trace(self) -> None:
        self.profiler[0]()
        self.tracing = True
        self.rec.span = (self.now(),)

    def _stop_trace(self) -> None:
        self.rec.span = (self.rec.span[0], self.now())
        self.profiler[1]()
        self.tracing = False

    def pump(self) -> None:
        """Runs at every window boundary of the engine and while idle."""
        from jax.profiler import TraceAnnotation

        eng = self.engine
        if self.feeder is not None:
            self.feeder.landed.clear()
            if self.feeder.error is not None:
                raise self.feeder.error
        with self.lock, TraceAnnotation("bench:upgrade"):
            upgraded = eng.upgrade_if_available()
            if upgraded and self.checksum is not None:
                self.rec.checksums.append((eng.stage, self.checksum(self.client.store)))
        if upgraded:
            ts = self.now()
            self.rec.upgrades.append((ts, eng.stage))
            if self.rec.ready_s is None:
                self.rec.ready_s = ts
        with TraceAnnotation("bench:submit"):
            if eng.params is not None:
                self._submit(self.now())
            self._collect(self.now())
        t = self.now()
        if self.profiler is not None and self.rec.span is None \
                and t >= self.mix.get("trace_start_s", 0.0):
            self._start_trace()
        dl = self._trace_deadline()
        if self.tracing and dl is not None and t >= dl:
            self._stop_trace()
        if t >= self.seconds:
            raise WindowClosed

    def _submit(self, t: float) -> None:
        from repro.serving.engine import PoolRequest

        eng = self.engine
        while self.next_req < len(self.reqs) and self.reqs[self.next_req].due_s <= t:
            r = self.reqs[self.next_req]
            rid = self.rid_base + self.next_req
            eng.submit(PoolRequest(rid=rid, prompt=r.prompt, max_new_tokens=r.out_len))
            self.rec.requests[rid] = {"rid": rid, "src": r.rid, "due": r.due_s,
                                      "submit": self.now(),
                                      "prompt_len": int(r.prompt.shape[0]),
                                      "out_len": r.out_len, "tokens": [],
                                      "finished": None}
            self.live.add(rid)
            self.next_req += 1

    def _collect(self, t: float) -> None:
        """Token values become visible to the client at each flush."""
        eng = self.engine
        done = []
        for rid in self.live:
            out = eng.outputs.get(rid)
            r = self.rec.requests[rid]
            if out is not None and len(out) > len(r["tokens"]):
                r["tokens"].extend([t] * (len(out) - len(r["tokens"])))
            if rid in eng.completed:
                r["finished"] = t
                done.append(rid)
        self.live.difference_update(done)


# -- set-up ------------------------------------------------------------------------

def make_checksum(store):
    """A jitted per-tensor checksum of the store's accumulators, with the
    same arithmetic as ``reference.checksum``."""
    import jax
    import jax.numpy as jnp

    specs = tuple((np.dtype(s.container).name, s.offset, s.size) for s in store.slots)

    @jax.jit
    def cs(buffers):
        out = []
        for dt, off, size in specs:
            q = jax.lax.dynamic_slice_in_dim(buffers[dt], off, size).astype(jnp.uint32)
            w = (jnp.arange(size, dtype=jnp.uint32) % jnp.uint32(65521)) + 1
            out.append(jnp.stack([jnp.sum(q * w, dtype=jnp.uint32),
                                  jnp.sum(q, dtype=jnp.uint32)]))
        return jnp.stack(out)

    keys = [s.key for s in store.slots]
    fn = lambda st: cs(dict(st.buffers))  # noqa: E731
    fn.keys = keys
    return fn


def feed_until(client, blob: memoryview, start: int, stages: int) -> int:
    """Feed the wire from ``start`` in large pieces until ``stages``
    stages are complete (or the wire ends); returns the next offset."""
    off = start
    while client.stages_complete < stages and off < len(blob):
        client.feed(blob[off:off + SETUP_FEED_BYTES])
        off += SETUP_FEED_BYTES
    return off


def warm_engine(engine, vocab: int) -> None:
    """Compile the cell's own decode and prefill-chunk executables, and
    the flush of every window length up to the dispatch window: one
    request of two chunks and three windows of tokens, then one short
    request per shorter window, each submitted and run to the end
    through the public API (rids -1, -2, ...)."""
    from repro.serving.engine import PoolRequest

    n = engine.prefill_chunk * 2 + 1
    prompt = (np.arange(n, dtype=np.int64) * 7919 % vocab).astype(np.int32)
    budgets = [engine.dispatch_window * 3] + list(range(1, engine.dispatch_window))
    for i, k in enumerate(budgets):
        engine.submit(PoolRequest(rid=-1 - i, prompt=prompt if i == 0 else prompt[:3],
                                  max_new_tokens=k))
        engine.run()


# -- the whole run -------------------------------------------------------------------

def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, root: Path = REPO, control: bool = False,
             log=print) -> dict:
    """One run of a cell named in BENCHMARK.json; returns the result
    object (the contract's last line)."""
    import jax

    bench = load_benchmark(root)
    spec = cell_spec(bench, workload, root)
    dev = jax.devices()[0]
    return serve_and_check(spec["cfg"], spec["mix"], spec["limits"],
                           metric_names(bench, workload, trace), seed, seconds,
                           trace, t_start, peaks=load_peaks(dev.device_kind),
                           control=control, log=log)


def setup_cell(cfg: dict, mix: dict, seed: int, log=print):
    """Everything before the window: weights from the seed, the server
    side (divide, encode), and warm-up. A cell with a stream warms up on
    a throwaway client and engine, so its window client sees no byte
    before the window; a cell without one ingests the whole wire into
    the window's own engine and warms that up."""
    import types

    import jax

    dev = jax.devices()[0]
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    comp = Compiles()
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; compile cache {cache_dir}")

    from repro.core import wire
    from repro.models.model import build_model
    from repro.serving.engine import SlotPoolEngine, WireStoreReceiver
    from repro.transmission.client import ProgressiveClient
    from bench import weights

    t = time.perf_counter()
    arch = arch_config(cfg)
    model = build_model(arch)
    params = weights.to_program_tree(cfg, weights.make_flat(cfg, seed))
    jax.block_until_ready(params)
    log(f"[setup] weights {time.perf_counter() - t:.1f}s")
    t = time.perf_counter()
    from repro.core.progressive import divide
    prog = divide(params, policy(cfg))
    del params
    log(f"[setup] divide {time.perf_counter() - t:.1f}s")
    t = time.perf_counter()
    blob = wire.encode(prog)
    log(f"[setup] encode {time.perf_counter() - t:.1f}s, {len(blob)} wire bytes")
    view = memoryview(blob)
    pool = dict(n_slots=mix["slots"], max_len=mix["max_len"], resident="quantized")

    t = time.perf_counter()
    streaming = mix.get("stream") is not None
    if streaming:
        # warm-up on a throwaway client and engine fed stage 1 of the
        # same wire, so the OR, the refresh and both steps compile (later
        # stages run the same programs on new values); the window's
        # client then sees no byte before the window opens
        wc = ProgressiveClient()
        feed_until(wc, view, 0, 1)
        we = SlotPoolEngine(model, prog, receiver=WireStoreReceiver(wc, prog), **pool)
        we.upgrade_if_available()
        warm_engine(we, cfg["vocab"])
        checksum = make_checksum(wc.store)
        jax.block_until_ready(checksum(wc.store))
        del we, wc
        gc.collect()
        client = ProgressiveClient()
        engine = SlotPoolEngine(model, prog, receiver=WireStoreReceiver(client, prog), **pool)
    else:
        checksum = None
        client = ProgressiveClient()
        feed_until(client, view, 0, prog.n_stages)
        engine = SlotPoolEngine(model, prog, receiver=WireStoreReceiver(client, prog), **pool)
        engine.upgrade_if_available()
        warm_engine(engine, cfg["vocab"])
    jax.block_until_ready(engine.caches)
    log(f"[setup] {'warm-up' if streaming else 'ingest and warm-up'} "
        f"{time.perf_counter() - t:.1f}s; stage {engine.stage}/{prog.n_stages}; "
        f"compiles and cache loads so far {comp.n} ({comp.secs['compile']:.1f}s), of them "
        f"persistent-cache hits {comp.hits}")

    return types.SimpleNamespace(comp=comp, model=model, prog=prog, blob=blob,
                                 view=view, client=client, engine=engine,
                                 checksum=checksum, streaming=streaming)


def serve_and_check(cfg: dict, mix: dict, limits: dict, metric_list: list,
                    seed: int, seconds: float, trace: bool, t_start: float, *,
                    peaks: dict, control: bool = False, log=print) -> dict:
    """Set-up, the window, the metrics and the check, for one cell given
    by its configuration, mix and limits."""
    import jax

    dev = jax.devices()[0]
    kind = dev.device_kind
    cell = setup_cell(cfg, mix, seed, log)
    comp, prog, engine, client = cell.comp, cell.prog, cell.engine, cell.client
    checksum, streaming, view = cell.checksum, cell.streaming, cell.view
    reqs = traffic_mod.generate(mix, seed, seconds, cfg["vocab"])

    tracer = None
    prof = None
    if trace:
        from jax import profiler
        tracer = tempfile.mkdtemp(prefix="bench_trace_")
        opts = profiler.ProfileOptions()
        opts.python_tracer_level = 0
        prof = (lambda: profiler.start_trace(tracer, profiler_options=opts),
                profiler.stop_trace)
    gc.collect()
    n0, h0, secs0 = comp.mark()
    comp.start_naming()
    setup_s = time.perf_counter() - t_start
    win = Window(engine, client, mix, reqs, seconds,
                 blob=view if streaming else None, profiler=prof, checksum=checksum)
    rec = win.run()
    comp.stop_naming()
    hits = comp.hits - h0
    spent = {k: comp.secs[k] - secs0[k] for k in secs0}
    log(f"[window] {rec.window_s:.2f}s; compiles inside the window: {comp.n - n0 - hits}, "
        f"loads from the persistent cache: {hits}; traced and lowered: {comp.names}; "
        f"seconds spent tracing {spent['trace']:.3f}, lowering {spent['lower']:.3f}, "
        f"compiling or loading {spent['compile']:.3f}")
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    lateness = [r["submit"] - max(r["due"], rec.ready_s or 0.0)
                for r in rec.requests.values()]
    log(f"[window] generator lateness: max {max(lateness, default=0.0) * 1e3:.1f} ms, "
        f"mean {np.mean(lateness) * 1e3 if lateness else 0.0:.1f} ms over "
        f"{len(lateness)} submits; stage {engine.stage}/{prog.n_stages}; "
        f"chip memory peak {peak / 2**30:.2f} GiB")

    # what the check and the metrics need, before the program's state goes
    served = {rid: list(v) for rid, v in engine.outputs.items() if rid >= 0}
    admit = dict(engine.admit_stage)
    stage_log = {rid: list(v) for rid, v in engine.stage_log.items()}
    upgrade_log = [dict(u) for u in engine.upgrade_log]
    checks_prog = [(s, np.asarray(a)) for s, a in rec.checksums]
    keys = checksum.keys if checksum is not None else []
    n_stages = prog.n_stages
    del engine, client, prog, view, win, checksum, cell
    gc.collect()

    span = rec.span if trace else (0.0, rec.window_s)
    runview = RunView(cfg=cfg, mix=mix, peaks=peaks, rec=rec, setup_s=setup_s,
                      n_stages=n_stages, upgrade_log=upgrade_log,
                      trace=None, span=span)
    device = {"platform": dev.platform, "kind": kind, "count": len(jax.devices()),
              "memory_peak_bytes": peak}
    breakdown = None
    if tracer:
        from bench import trace as tr
        t = time.perf_counter()
        runview.trace = tr.read(tr.find_xplane(tracer))
        ops = runview.trace["ops"]
        busy = tr.busy_s(ops)
        device["busy_s"] = busy
        device["window_s"] = span[1] - span[0]
        breakdown = {"device_ops": tr.top_ops(ops), "idle_gaps": tr.idle_gaps(runview.trace)}
        log(f"[trace] {len(ops)} device ops, busy {busy:.3f}s of {span[1] - span[0]:.3f}s "
            f"traced from {span[0]:.2f}s; "
            f"read in {time.perf_counter() - t:.1f}s")
        import shutil
        shutil.rmtree(tracer, ignore_errors=True)

    metrics = {}
    for m in metric_list:
        v = load_reader(m["name"])(runview)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    from bench import correct
    t = time.perf_counter()
    checks = correct.check(cfg, mix, limits, seed, served, admit, stage_log,
                           prompts={rid: reqs[r["src"]].prompt
                                    for rid, r in rec.requests.items()},
                           checksums=checks_prog, checksum_keys=keys,
                           control=control, log=log)
    log(f"[check] {time.perf_counter() - t:.1f}s")
    ok = all(c["value"] <= c["limit"] for c in checks.values() if "limit" in c)
    attempted = sum(1 for r in rec.requests.values() if r["due"] <= rec.window_s)
    result = {"correct": bool(ok), "attempted": attempted, "failed": 0,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


@dataclasses.dataclass
class RunView:
    """What a metric reader sees."""

    cfg: dict
    mix: dict
    peaks: dict | None
    rec: Record
    setup_s: float
    n_stages: int
    upgrade_log: list
    trace: dict | None
    span: tuple          # the part of the window the metrics read (traced part)
