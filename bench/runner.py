"""One run of one cell: set-up, the open-loop window, the output check.

The served path is the program's own: requests go to
``repro.stream.AdmissionQueue`` with its worker thread running, which drains
them through ``BatchServer.serve`` into ``RecommendationEngine
.recommend_batch``.  The benchmark adds only what measures: a generator
thread that submits each request at its due time, a watcher thread that
stamps each result as it resolves, and a thin proxy that times ``serve``.

A live deployment (archive kind ``rolling``) also runs the program's
ingestion loop: ``LiveIngestor`` primes a ``RollingDeviceArchive`` from a
seeded collector (``bench/live.py``), every drain serves a version-pinned
snapshot of it, and ``IngestPump`` appends a column every
``ingest.period_s`` through the window.  The proxy records the version each
request was served from, and the harness when each version was published.

Every latency runs from the time a request was due, so a late generator or
a stall anywhere shows in it.  Tails are taken from every raw sample.

The objects set-up leaves behind (JAX's and the program's, some hundreds
of thousands) are frozen out of the garbage collector before the window:
otherwise each full collection in the window walks all of them, a pause of
its own that stops every thread.  The window keeps only the answers the
check samples.
"""
from __future__ import annotations

import contextlib
import gc
import math
import shutil
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, replace

import numpy as np

from bench import catalog, check, live as live_lib, trace as trace_lib, \
    traffic as traffic_lib

CLOCK = time.monotonic
TRACE_AT = 0.3          # the traced stretch starts this far into the window
TRACE_S = 5.0           # and lasts this long, or a third of the window
GRACE_S = 60.0          # how long answers due in the window are waited for


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Compiles:
    """Counts the programs JAX compiles or loads from its persistent cache
    (each is one backend-compile event), by time."""

    def __init__(self):
        import jax
        self.times: list = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.times.append(CLOCK())

    def between(self, a: float, b: float) -> int:
        return sum(1 for t in self.times if a <= t <= b)


_COMPILES: list = []


def compiles() -> Compiles:
    """The process's one compile counter (JAX's listeners are global)."""
    if not _COMPILES:
        _COMPILES.append(Compiles())
    return _COMPILES[0]


class ServeProxy:
    """Times every ``BatchServer.serve`` call the admission worker makes."""

    def __init__(self, server, annotate):
        self.server = server
        self.bucket_sizes = server.bucket_sizes
        self.annotate = annotate
        self.calls: list = []            # (start, end, n requests)
        self.started: dict = {}          # id(request) -> serve start

    def serve(self, target, requests):
        t0 = CLOCK()
        for r in requests:
            self.started[id(r)] = t0
        with self.annotate("bench.serve"):
            out = self.server.serve(target, requests)
        self.calls.append((t0, CLOCK(), len(requests)))
        return out


class LiveServeProxy(ServeProxy):
    """``ServeProxy`` that also records the archive version of each serve,
    per request (``versions``) and per call (``call_versions``), and when
    the drain's snapshot was taken (``snapped``), from the queue's
    ``resolve_archive`` as ``timed`` wraps it."""

    def __init__(self, server, annotate):
        super().__init__(server, annotate)
        self.versions: dict = {}         # id(request) -> version served
        self.snapped: dict = {}          # id(request) -> (began, ended)
        self.call_versions: list = []
        self._last = (float("nan"), float("nan"))

    def timed(self, resolve):
        """``resolve`` (a drain's snapshot) timed from call to return."""
        def resolve_archive():
            t0 = CLOCK()
            snap = resolve()
            self._last = (t0, CLOCK())
            return snap
        return resolve_archive

    def serve(self, target, requests):
        for r in requests:
            self.versions[id(r)] = target.version
            self.snapped[id(r)] = self._last
        out = super().serve(target, requests)
        self.call_versions.append(target.version)
        return out


@dataclass
class Run:
    """What one run recorded; the per-layer readers take their numbers
    from here."""

    seconds: float
    w0: float
    w1: float
    due: np.ndarray
    started: np.ndarray            # drain start per request (NaN: none)
    done: np.ndarray               # resolve time per request (NaN: none)
    serve_calls: list
    counters: dict
    bench: object
    peaks: dict
    trace: object = None

    def in_window(self, t) -> bool:
        return self.w0 <= t < self.w1


class System:
    """The program under test, built as the configuration says: its archive
    staged once, admission as the deployment runs it, shedding off (a
    ``PoolCache`` answer is a stale pool, not the one asked for)."""

    def __init__(self, cfg: dict, dep: catalog.Deployment, annotate):
        from repro.core import EngineConfig
        from repro.serve import BatchServer, DeviceArchive
        from repro.stream import AdmissionQueue
        arc = cfg["archive"]
        if arc["kind"] != "static":
            raise ValueError(f"archive kind {arc['kind']!r}: only a static "
                             "DeviceArchive is built")
        self.server = BatchServer(config=EngineConfig())
        self.proxy = ServeProxy(self.server, annotate)
        self.archive = DeviceArchive.stage(
            dep.cands, key=cfg["name"], precision=arc["precision"])
        self.queue = AdmissionQueue(
            self.proxy, self.archive,
            adaptive=bool(cfg["admission"]["adaptive"]), shed_depth=None)

    def warm(self, shapes, presets, requests) -> None:
        """Serve each (bucket, unique-mask pad) shape the window will use
        once, so nothing compiles in the window."""
        import jax
        archive = self.queue.resolve_archive()
        for bucket, u in shapes:
            self.server.serve(archive,
                              warm_requests(bucket, u, presets, requests))
        jax.effects_barrier()


class LiveSystem(System):
    """A live deployment, built from the program's own pieces:
    ``LiveIngestor(collector).prime()`` stages the rolling archive at the
    configuration's tier, the admission queue serves snapshots of it, and
    an ``IngestPump`` (started and stopped by the run) appends a column
    every ``ingest.period_s``.  The pump's poll is wrapped to record when
    each version's poll began and returned (``began``, ``published``), and
    the queue's ``resolve_archive`` to time each drain's snapshot."""

    def __init__(self, cfg: dict, dep: catalog.Deployment, annotate):
        from repro.core import EngineConfig
        from repro.serve import BatchServer
        from repro.stream import AdmissionQueue, IngestPump, LiveIngestor
        arc = cfg["archive"]
        self.annotate = annotate
        self.server = BatchServer(config=EngineConfig())
        self.proxy = LiveServeProxy(self.server, annotate)
        self.stand_in = live_lib.Collector(dep)
        self.ingestor = LiveIngestor(
            self.stand_in, window=dep.window, precision=arc["precision"],
            headroom=float(arc["headroom"]))
        t = CLOCK()
        self.archive = self.ingestor.prime()
        self.began, self.published = {0: t}, {0: CLOCK()}
        self._poll = self.ingestor.poll
        self.ingestor.poll = self._publish
        self.pump = IngestPump(self.ingestor, self.stand_in.collect,
                               period=float(cfg["ingest"]["period_s"]))
        self.queue = AdmissionQueue(
            self.proxy, self.archive,
            adaptive=bool(cfg["admission"]["adaptive"]), shed_depth=None)
        self.queue.resolve_archive = self.proxy.timed(
            self.queue.resolve_archive)

    def _publish(self) -> int:
        t0 = CLOCK()
        with self.annotate("bench.tick"):
            n = self._poll()
        t1 = CLOCK()
        for v in range(self.ingestor.version - n + 1,
                       self.ingestor.version + 1):
            self.began[v], self.published[v] = t0, t1
        return n

    def warm(self, shapes, presets, requests) -> None:
        """Absorb one tick, so the append programs compile here, then
        serve every warmed shape against a snapshot."""
        self.stand_in.collect()
        self.ingestor.poll()
        super().warm(shapes, presets, requests)

    def window(self, requests, sample, results, w0: float, w1: float):
        """After the window: the version that served each sampled request,
        the check's ``versions_off`` over every answer, and the window's
        ingest numbers."""
        p = self.proxy
        versions = [p.versions.get(id(requests[i])) for i in sample]
        stamps = {id(requests[i]): rec.diagnostics.get("archive_version")
                  for i, rec in results.items()}
        rows = [(p.versions[id(r)], stamps.get(id(r)), *p.snapped[id(r)])
                for r in requests if id(r) in p.versions]
        off = check.versions_off(rows, self.published, self.began)
        calls = sorted(zip((c[0] for c in p.calls), p.call_versions))
        fresh = []
        for v, t in self.published.items():
            if w0 <= t < w1:
                first = next((a for a, cv in calls if a >= t and cv >= v),
                             None)
                if first is not None:
                    fresh.append(first - t)
        info = {"ticks": self.pump.ticks_pumped,
                "pump_errors": self.pump.errors,
                "clipped_samples": self.archive.clipped_samples,
                "fresh_ms": (float(np.median(fresh)) * 1e3 if fresh
                             else None),
                "versions_served": [min(p.call_versions, default=None),
                                    max(p.call_versions, default=None)]}
        if self.pump.last_error is not None:
            info["pump_error"] = repr(self.pump.last_error)
        return versions, off, info


def warm_requests(bucket: int, u: int, presets: list, requests: list):
    """``bucket`` requests whose distinct masks pad to ``u`` unique rows."""
    distinct = 1 if u == 1 else u // 2 + 1
    if distinct > bucket or distinct - 1 > len(presets):
        raise ValueError(f"cannot warm bucket {bucket} with {u} unique "
                         f"masks from {len(presets)} presets")
    filters = [{}] + presets[:distinct - 1]
    out = []
    for i in range(bucket):
        r = requests[i % len(requests)]
        plain = dict(regions=None, azs=None, families=None,
                     categories=None, types=None)
        out.append(replace(r, **{**plain, **filters[i % distinct]}))
    return out


class OpenLoop:
    """The window's clients: a generator thread submits each request at its
    due time, whatever the server does, and a watcher thread stamps each
    answer as it resolves.  Only the answers in ``keep`` are held on to."""

    def __init__(self, system: System, tr, keep, annotate):
        self.system, self.tr, self.annotate = system, tr, annotate
        n = len(tr.requests)
        self.keep = frozenset(int(i) for i in keep)
        self.tickets: list = []
        self.done = np.full(n, np.nan)
        self.results: dict = {}          # index in keep -> Recommendation
        self.errors: dict = {}           # index -> the error it resolved with
        self.lateness = np.zeros(n)
        self._stop = threading.Event()
        self.w0 = CLOCK() + 0.05
        self.due = self.w0 + tr.due
        self._threads = [threading.Thread(target=self._generate,
                                          name="bench-generator"),
                         threading.Thread(target=self._watch,
                                          name="bench-watcher")]

    def _generate(self):
        for i, req in enumerate(self.tr.requests):
            wait = self.due[i] - CLOCK()
            if wait > 0:
                time.sleep(wait)
            with self.annotate("bench.submit"):
                self.tickets.append(self.system.queue.submit(
                    req, at=self.due[i]))
            self.lateness[i] = CLOCK() - self.due[i]

    def _watch(self):
        i, n = 0, len(self.done)
        while i < n and not self._stop.is_set():
            if i >= len(self.tickets):
                time.sleep(max(self.due[i] - CLOCK(), 0.0002))
                continue
            try:
                rec = self.tickets[i].result(timeout=0.1)
                if i in self.keep:
                    self.results[i] = rec
            except TimeoutError:
                continue
            except Exception as err:  # noqa: BLE001 - a failed answer
                self.errors[i] = err
            self.done[i] = CLOCK()
            self.tickets[i] = None
            i += 1

    def start(self) -> None:
        self.system.queue.start()
        for t in self._threads:
            t.start()

    def close(self, w1: float) -> None:
        """At the window's end every request has been submitted."""
        time.sleep(max(0.0, w1 - CLOCK()))
        self._threads[0].join()

    def wait_for(self, idx) -> None:
        deadline = CLOCK() + GRACE_S
        while CLOCK() < deadline and any(math.isnan(self.done[i])
                                         for i in idx):
            time.sleep(0.01)

    def stop(self) -> float:
        self._stop.set()
        self._threads[1].join()
        t_end = CLOCK()
        self.system.queue.stop(drain=False)
        return t_end


def p99(values) -> float:
    """The 99th percentile of every raw sample (linear interpolation)."""
    return float(np.percentile(np.asarray(values, np.float64), 99.0))


def run_cell(bench, cell, seed: int, seconds: float, traced: bool,
             t_start: float, cache_dir: str | None,
             require_tpu: bool = True, fault=None,
             control: str | None = None) -> dict:
    """One run of ``cell``; returns the result line (a dict).

    ``fault`` breaks the built system before warm-up (the tests' faults);
    ``control`` also answers the sample with the reference at that lower
    precision in the program's place, and reports its numbers (the
    readings that set the check's limits).
    """
    import jax
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and (dev.platform != "tpu" or len(devices) < cell.chips):
        raise SystemExit(
            f"bench: the cell needs {cell.chips} TPU chip(s); JAX found "
            f"{len(devices)} {dev.platform} device(s)")
    peaks = bench.peaks(dev.device_kind) if require_tpu else {}
    if cache_dir is not None:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    comp = compiles()
    cfg, tcfg = cell.config, cell.traffic
    annotate = (jax.profiler.TraceAnnotation if traced
                else lambda _name: contextlib.nullcontext())

    live = cfg["archive"]["kind"] == "rolling"
    dep = catalog.build(cfg, seed,
                        live_lib.ticks_for(cfg, seconds) if live else 0)
    tr = traffic_lib.build(tcfg, dep.cands, seconds, seed)
    n = len(tr.requests)
    amounts = np.array([r.amount for r in tr.requests])
    sample = check.draw_sample(n, int(tcfg["check"]["sample"]), seed, amounts)
    system = (LiveSystem if live else System)(cfg, dep, annotate)
    if fault is not None:
        fault(system)
    system.warm(tcfg["warm"], tr.presets, tr.requests)
    stats0 = (system.server.stats.requests, system.server.stats.batches,
              system.server.stats.padded_slots,
              dict(system.server.stats.bucket_counts))
    gc.collect()
    gc.freeze()
    setup_s = CLOCK() - t_start
    log(f"set-up {setup_s:.3f} s: K={len(dep.cands.names)} "
        f"T={dep.window}, {n} requests due in {seconds} s")

    loop = OpenLoop(system, tr, sample, annotate)
    loop.start()
    if live:
        system.pump.start()
    tr_dir = None
    if traced:
        span = min(TRACE_S, seconds / 3.0)
        time.sleep(max(0.0, loop.w0 + TRACE_AT * seconds - CLOCK()))
        tr_dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(tr_dir)
        with jax.profiler.TraceAnnotation(trace_lib.WINDOW_SPAN):
            time.sleep(span)
        jax.profiler.stop_trace()
    w0, w1 = loop.w0, loop.w0 + seconds
    loop.close(w1)
    if live:
        system.pump.stop()
    # A latency cell's tail is the tail of every request due in the window,
    # so it waits for all of them; the throughput cell waits for its sample.
    waits_all = any(m["name"] == "p99_ms" for m in cell.end_to_end)
    loop.wait_for(range(n) if waits_all else sample)
    t_end = loop.stop()
    in_window = comp.between(w0, t_end)
    done, due, lateness = loop.done, loop.due, loop.lateness
    failed = np.zeros(n, bool)
    failed[list(loop.errors)] = True
    ok = ~np.isnan(done) & ~failed
    # an answer that never came counts with the wait it imposed so far
    lat = np.where(ok, done, t_end) - due
    e2e = {}
    names = {m["name"] for m in cell.end_to_end}
    if "p99_ms" in names:
        e2e["p99_ms"] = p99(lat) * 1e3
    if "rps" in names:
        e2e["rps"] = float(np.sum(done[ok] < w1)) / seconds
    e2e["setup_s"] = setup_s
    st = system.server.stats
    counters = {"requests": st.requests - stats0[0],
                "batches": st.batches - stats0[1],
                "padded_slots": st.padded_slots - stats0[2],
                "batches_by_bucket": {
                    str(b): c - stats0[3].get(b, 0)
                    for b, c in sorted(st.bucket_counts.items())}}
    started = np.array([system.proxy.started.get(id(r), np.nan)
                        for r in tr.requests])
    run = Run(seconds, w0, w1, due, started, done, system.proxy.calls,
              counters, bench, peaks)
    mem = dev.memory_stats() or {}
    peak = int(mem.get("peak_bytes_in_use", 0))
    served = [(tr.requests[i], loop.results.get(i), loop.errors.get(i))
              for i in sample]
    versions = ingest = None
    if live:
        versions, v_off, ingest = system.window(tr.requests, sample,
                                                loop.results, w0, w1)
    del system, loop
    gc.unfreeze()
    gc.collect()

    numbers = check.compare(cfg, dep, served, versions)
    ctl = (check.compare(cfg, dep, check.control_answers(
        cfg, dep, served, control, versions), versions)
        if control else None)
    if live:
        numbers["versions_off"] = (float(v_off),
                                   float(cfg["limits"]["versions_off"]))
        if ctl is not None:
            ctl["versions_off"] = numbers["versions_off"]
    if waits_all:
        numbers["unanswered"] = (float(n - ok.sum()),
                                 numbers["unanswered"][1])
    correct = all(v <= lim for v, lim in numbers.values())
    log(f"window: {n} due, {int(ok.sum())} answered, "
        f"{int(failed.sum())} failed; generator late by "
        f"p50 {np.median(lateness) * 1e3:.3f} ms, max "
        f"{lateness.max() * 1e3:.3f} ms; {in_window} compiles in the "
        f"window; peak {peak} bytes")

    metrics = {}
    if traced:
        run.trace = trace_lib.load(tr_dir, chips=cell.chips)
        shutil.rmtree(tr_dir, ignore_errors=True)
        for m in cell.per_layer:
            v = bench.reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                  "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": n,
           "failed": int(failed.sum()),
           "metrics": metrics, "device": device,
           "compiles_in_window": in_window,
           "window": {
               "offered": n,
               "answered_in_window": int(np.sum(done[ok] < w1)),
               "backlog_1s": int(np.sum((due < w1 - 1.0) & ~(done < w1))),
               "late_p50_ms": float(np.median(lateness)) * 1e3,
               "late_max_ms": float(lateness.max()) * 1e3,
               "late_max_at_s": float(tr.due[np.argmax(lateness)]),
               "serve_max_ms": max((b - a for a, b, _ in run.serve_calls
                                    if run.in_window(a)), default=0.0) * 1e3,
               "batches_by_bucket": counters["batches_by_bucket"]}}
    if ingest is not None:
        out["window"].update(ingest)
    if ctl is not None:
        out["control"] = {k: v for k, (v, _) in ctl.items()}
    if traced:
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s
        out["breakdown"] = trace_lib.breakdown(run.trace)
    for name, (v, lim) in numbers.items():
        log(f"check {name} {v!r} limit {lim!r}")
    out["check"] = {k: {"value": v, "limit": lim}
                    for k, (v, lim) in numbers.items()}
    return out
