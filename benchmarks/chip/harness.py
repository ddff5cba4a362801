"""One run of one cell: set-up, warm-up, the measured window, the check.

Everything a cell needs is found by name from ``BENCHMARK.json``:

* ``configs/<config>.json``: the sizes, and under ``tiny`` the sizes
  the CPU tests run the configuration at; ``configs/<config>.py``: its
  generator of named sets (``generate``) and of the program's bitmaps
  (``postings``);
* ``traffic/<traffic>.json``: the loop (``closed`` with ``clients``, or
  ``open`` with ``rate``), the server's settings and the mix of one
  entry, read by ``traffic.py``;
* ``kinds/<kind>.py``, the mix entry's kind: ``queries(entry, sets,
  rng)``, ``to_query(q)`` (the program's ``Query``), ``reference(sets)``,
  ``expected(ref, q)``, ``control(ref, q)`` (the reference with the
  exactness the configuration states broken, for ``control.py``) and
  ``same(got, want)``;
* ``metrics/<metric>.py``: one reader per metric;
* the cell's ``chips``: above 1, the run installs a wide mesh over that
  many devices, stripes the arena's rows over them and serves through
  ``QueryServer(mesh=)``.

Adding a cell, a configuration, a kind or a metric adds files; nothing
here changes.

The entry the window drives is the served path: ``QueryServer.submit``
and ``QueryServer.step`` over an arena-backed ``InvertedIndex``, with
no backend override.  The client is this module's loop, single-threaded
like the server.  In a closed loop each of the cell's clients sends its
next query as soon as its previous one resolved, and the loop runs one
server tick while work is queued.  In an open loop the client sends
every query whose due time has passed, runs one server tick while work
is queued, and sleeps to the next due time when none is.  Latency runs
from a query's due time to its ``resolved_at`` (the answer on the
host), on the same monotonic clock, so a long tick counts against the
queries that came due during it.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import traffic as traffic_gen  # noqa: E402

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
DRAIN_S = 60.0                # wait for late answers past the close
CHECK_SAMPLE = 256            # answers compared with the reference per run
WARM_PASS_S = 3.0             # one closed-loop warm-up pass
WARM_QUIET_PASSES = 2         # warm-up ends after this many passes in a
WARM_MAX_S = 120.0            # row compile nothing, or gives up after this
ROW_BYTES = 8192
ADOPT_CHUNK = 4096            # bitmaps per bulk promotion into the arena


class BenchError(Exception):
    """The run cannot produce a result (no chip, bad manifest)."""


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------

def load_manifest(root: Path) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


# by path: the standard library has a module named ``trace`` too
trace_red = _module(HERE / "trace.py", "chip_trace")


def resolve(manifest: dict, workload: str, base: Path = HERE) -> dict:
    """The cell's config, traffic, kind module and metric entries, found
    by name under ``base`` (this directory)."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    cfg_json = base / "configs" / f"{cell['config']}.json"
    cfg = json.loads(cfg_json.read_text())

    def metrics(group):
        return [m for m in manifest[group]
                if workload in m.get("workloads", [workload])]

    traffic = traffic_gen.load(cell["traffic"], base)
    (entry,) = traffic["mix"]
    return {"cell": cell, "config_entry": cfg_entry, "config": cfg,
            "generator": _module(base / "configs" / f"{cell['config']}.py",
                                 f"config_{cell['config']}"),
            "traffic": traffic,
            "kind": _module(base / "kinds" / f"{entry['kind']}.py",
                            f"kind_{entry['kind']}"),
            "end_to_end": metrics("end_to_end"),
            "per_layer": metrics("per_layer")}


def reader(name: str, base: Path = HERE):
    return _module(base / "metrics" / f"{name}.py",
                   "metric_" + name.replace(".", "_"))


# ---------------------------------------------------------------------------
# counters the run keeps
# ---------------------------------------------------------------------------

class CompileCounter:
    """Counts XLA compilations and their seconds (a persistent-cache load
    counts too): every call of a jitted function at a new shape costs
    one."""

    _registered: "CompileCounter | None" = None

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self.misses = 0             # of them, not found in the cache

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._registered is None:
            from jax import monitoring
            counter = cls()

            def on_duration(event, duration, **kwargs):
                if event == BACKEND_COMPILE_EVENT:
                    counter.count += 1
                    counter.seconds += duration

            def on_event(event, **kwargs):
                if event == CACHE_MISS_EVENT:
                    counter.misses += 1

            monitoring.register_event_duration_secs_listener(on_duration)
            monitoring.register_event_listener(on_event)
            cls._registered = counter
        return cls._registered


@dataclasses.dataclass
class Rec:
    """One query the client sent."""
    query: dict
    due: float                  # monotonic due time
    submitted: float            # monotonic, when submit() began
    ticket: object


def _quantiles(xs) -> dict:
    if not len(xs):
        return {"n": 0}
    a = np.asarray(xs, np.float64)
    return {"n": int(a.size), "p50": float(np.percentile(a, 50)),
            "p95": float(np.percentile(a, 95)), "max": float(a.max())}


# ---------------------------------------------------------------------------
# the client loops
# ---------------------------------------------------------------------------

class Client:
    """Drives a ``QueryServer`` with one cell's traffic; with ``spans``
    each call into the server sits in a ``bench.*`` trace span."""

    def __init__(self, server, to_query, spans: bool):
        self.server = server
        self.to_query = to_query
        self.ticks: list[float] = []
        if spans:
            from jax.profiler import TraceAnnotation
            self._span = TraceAnnotation
        else:
            self._span = lambda name: contextlib.nullcontext()

    def submit(self, q: dict, due: float) -> Rec:
        with self._span("bench.submit"):
            t0 = time.monotonic()
            ticket = self.server.submit(self.to_query(q))
        return Rec(q, due, t0, ticket)

    def step(self) -> None:
        with self._span("bench.step"):
            t0 = time.monotonic()
            self.server.step()
            self.ticks.append(time.monotonic() - t0)

    def wait_until(self, t: float) -> None:
        with self._span("bench.wait"):
            while True:
                left = t - time.monotonic()
                if left <= 0:
                    return
                time.sleep(left)

    def drain(self, deadline: float) -> None:
        while self.server.pending and time.monotonic() < deadline:
            self.step()

    def open_loop(self, queries, dues, start: float, end: float
                  ) -> list[Rec]:
        """Send each query at its due time (``start`` plus the next of
        ``dues``) until ``end``, whether or not earlier ones resolved;
        between sends, one server tick while work is queued."""
        it = iter(queries)
        recs = []
        due = start + next(dues)
        self.wait_until(start)
        while True:
            now = time.monotonic()
            if now >= end:
                return recs
            while due <= now:
                recs.append(self.submit(next(it), due))
                due = start + next(dues)
            if self.server.pending:
                self.step()
            elif due < end:
                self.wait_until(due)
            else:
                self.wait_until(end)

    def closed_loop(self, queries, clients: int, start: float, end: float
                    ) -> list[Rec]:
        """``clients`` callers, each sending its next query as soon as
        its previous one resolved, until ``end``."""
        it = iter(queries)          # a list, or an endless generator
        self.wait_until(start)
        recs = [self.submit(next(it), start) for _ in range(clients)]
        live = list(range(len(recs)))
        while time.monotonic() < end:
            self.step()
            still = []
            for j in live:
                if recs[j].ticket.done:
                    if time.monotonic() < end:
                        now = time.monotonic()
                        recs.append(self.submit(next(it), now))
                        still.append(len(recs) - 1)
                else:
                    still.append(j)
            live = still
        return recs


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

class Run:
    """Set-up, warm-up, window and check of one cell for one seed.

    ``overrides`` replaces configuration sizes (the CPU tests run every
    cell at its configuration's ``tiny`` size through it); the
    benchmark's own runs pass none.  ``base`` is where the cell's files
    are found (this directory); ``chips`` the devices it runs on, by
    default the cell's own.
    """

    def __init__(self, root: Path, workload: str, seed: int, seconds: float,
                 trace: bool, *, t_start: float, overrides=None,
                 manifest=None, log=None, base: Path = HERE,
                 chips: int | None = None):
        self.root = Path(root)
        self.base = Path(base)
        self.manifest = manifest or load_manifest(self.root)
        self.spec = resolve(self.manifest, workload, self.base)
        self.chips = int(chips or self.spec["cell"]["chips"])
        self.cfg = dict(self.spec["config"], **(overrides or {}))
        self.traffic = self.spec["traffic"]
        self.kind = self.spec["kind"]
        self.workload = workload
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.t_start = t_start
        self.log = log or (lambda *a: print(
            f"[{time.monotonic() - t_start:8.3f} s]", *a, flush=True))

    # -- set-up ---------------------------------------------------------

    def setup(self) -> None:
        import jax
        from repro.core import BitmapArena
        from repro.data.index import InvertedIndex
        from repro.serve import QueryServer
        self.jax = jax
        self.compiles = CompileCounter.get()
        t0 = time.monotonic()
        gen = self.spec["generator"]
        self.sets = gen.generate(self.cfg, traffic_gen.stream_rng(self.seed, 0))
        self.log("sets generated")
        postings = gen.postings(self.sets)
        rows = sum(len(bm.containers) for bm in postings.values())
        self.log("bitmaps made")
        self.arena = BitmapArena(capacity=rows + 1)
        # ``from_postings`` promotes through ``BitmapArena.adopt_frozen``;
        # one call over every bitmap costs time quadratic in the values
        # (``containers_to_word_rows`` masks all of them once per block of
        # 256 rows: 924 s for the NETFLIX twin on a v5e host), so the same
        # bulk promotion runs here in pieces, and ``from_postings`` finds
        # every bitmap resident
        bms = list(postings.values())
        for lo in range(0, len(bms), ADOPT_CHUNK):
            self.arena.adopt_frozen(bms[lo:lo + ADOPT_CHUNK])
        del bms
        self.index = InvertedIndex.from_postings(postings, self.sets.universe,
                                                 arena=self.arena)
        self.log("index built")
        self.mesh = None
        if self.chips > 1:
            from repro.dist import ctx
            self.mesh = ctx.install_wide_mesh(self.chips)
            self.arena.shard_slabs(self.mesh).sync()
        else:
            self.arena.sync()
        sizes = self.sets.sizes()
        self.log(f"data: {len(self.sets)} sets of {int(sizes.sum())} values "
                 f"(largest {int(sizes.max())}), {rows} container rows, "
                 f"{self.arena.capacity * ROW_BYTES} slab bytes, "
                 f"built in {time.monotonic() - t0:.3f} s")
        self.server = QueryServer(self.index, mesh=self.mesh,
                                  **self.traffic.get("server", {}))

        self.to_query = self.kind.to_query
        self.window_queries = traffic_gen.queries(
            self.traffic, self.kind, self.sets, self.seed, traffic_gen.WINDOW)
        self.warm_queries = traffic_gen.queries(
            self.traffic, self.kind, self.sets, self.seed,
            traffic_gen.WARM_UP)
        self.warm_up()

    def drive(self, client: Client, queries, stream: int, start: float,
              end: float) -> list[Rec]:
        """The cell's own loop from ``start`` to ``end``; an open loop's
        arrivals come from the arrival stream that goes with ``stream``."""
        tr = self.traffic
        if tr["loop"] == "closed":
            return client.closed_loop(queries, tr["clients"], start, end)
        return client.open_loop(
            queries, traffic_gen.arrivals(tr, self.seed, stream), start, end)

    def warm_up(self) -> None:
        """Drive the cell's own loop with the warm-up stream's queries,
        in passes of ``WARM_PASS_S``, until ``WARM_QUIET_PASSES`` passes
        in a row compile nothing (or ``WARM_MAX_S`` has gone by).  The
        window's queries are not among them: whatever the window meets
        first, it meets as a user would."""
        client = Client(self.server, self.to_query, spans=False)
        missed = self.compiles.misses
        passes: list[int] = []
        t_end = time.monotonic() + WARM_MAX_S
        while passes[-WARM_QUIET_PASSES:] != [0] * WARM_QUIET_PASSES \
                and time.monotonic() < t_end:
            n = self.compiles.count
            start = time.monotonic()
            self.drive(client, self.warm_queries, traffic_gen.WARM_UP,
                       start, start + WARM_PASS_S)
            client.drain(time.monotonic() + DRAIN_S)
            passes.append(self.compiles.count - n)
        self.log(f"warm-up passes: {len(passes)}, compiles per pass: "
                 f"{passes}, not in the compile cache: "
                 f"{self.compiles.misses - missed}")

    # -- the window -----------------------------------------------------

    def window(self) -> None:
        jax = self.jax
        self.trace_dir = self.root / ".bench" / "trace" / self.workload
        if self.trace:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            self.trace_dir.mkdir(parents=True)
            trace_red.start(str(self.trace_dir))
        client = Client(self.server, self.to_query, spans=self.trace)
        stats0 = self.server.stats()
        arena0 = dataclasses.replace(self.arena.stats)
        compiles0 = self.compiles.count
        compile_s0 = self.compiles.seconds
        misses0 = self.compiles.misses
        gc0 = [g["collections"] for g in gc.get_stats()]
        start = time.monotonic() + 0.01
        self.start, self.end = start, start + self.seconds
        self.setup_s = start - self.t_start
        span = (jax.profiler.TraceAnnotation("bench.window") if self.trace
                else contextlib.nullcontext())
        with span:
            recs = self.drive(client, self.window_queries,
                              traffic_gen.WINDOW, start, self.end)
        if self.trace:
            jax.profiler.stop_trace()
        self.window_gcs = [g["collections"] - c
                           for g, c in zip(gc.get_stats(), gc0)]
        self.log("window closed")
        client.drain(time.monotonic() + DRAIN_S)
        self.log("drained")
        self.window_compiles = self.compiles.count - compiles0
        self.window_compile_s = self.compiles.seconds - compile_s0
        self.window_misses = self.compiles.misses - misses0
        self.recs, self.ticks = recs, client.ticks
        self.stats0, self.stats1 = stats0, self.server.stats()
        self.arena0, self.arena1 = arena0, dataclasses.replace(
            self.arena.stats)
        devs = (list(self.mesh.devices.flat) if self.mesh is not None
                else jax.devices()[:1])
        self.device_kind = devs[0].device_kind
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in devs]
        if len(devs) > 1:
            for d, p in zip(devs, peaks):
                print(f"memory_peak_bytes of {d}: {p}", file=sys.stderr)
        known = [p for p in peaks if p is not None]
        self.memory_peak = max(known) if known else None
        self.reduced = None
        if self.trace:
            path = trace_red.find_xplane(str(self.trace_dir))
            if path is not None:
                self.reduced = trace_red.reduce(path)
            self.log("trace reduced")

    # -- what the window shows -------------------------------------------

    def summary(self) -> dict:
        """Latency, rate and failure counts over every query sent in the
        window (answers that came after the close count, late).  Nothing
        is sent after the close; ``last`` is when the last answer of what
        was sent came back, so ``ok / (last - start)`` is a rate over all
        the work and all the time it took."""
        from repro.serve import OK
        lat, ok, ok_in_window, failed, lost = [], 0, 0, 0, 0
        last = self.end
        for r in self.recs:
            t = r.ticket
            if not t.done:
                lost += 1
                failed += 1
                continue
            lat.append(t.telemetry.resolved_at - r.due)
            last = max(last, t.telemetry.resolved_at)
            if t.result.status != OK or t.telemetry.degraded:
                failed += 1
                continue
            ok += 1
            ok_in_window += t.telemetry.resolved_at <= self.end
        return {"latency_s": lat, "ok": ok, "ok_in_window": ok_in_window,
                "last": last, "failed": failed, "lost": lost,
                "host_fallbacks": self.stats1.host_fallbacks
                - self.stats0.host_fallbacks}

    def report(self) -> None:
        s = self.summary()
        batch = [r.ticket.telemetry.batch_size for r in self.recs
                 if r.ticket.done]
        self.log(f"queries sent in window: {len(self.recs)}, ok: {s['ok']} "
                 f"({s['ok_in_window']} by the close, the last "
                 f"{s['last'] - self.end:.3f} s after it), "
                 f"failed: {s['failed']}, "
                 f"host fallbacks: {s['host_fallbacks']}")
        self.log(f"compiles in window: {self.window_compiles} "
                 f"({self.window_compile_s:.3f} s, "
                 f"{self.window_misses} not in the compile cache)")
        self.log(f"batch size: {json.dumps(_quantiles(batch))}")
        self.log("send lateness, s after the due time: " + json.dumps(
            _quantiles([r.submitted - r.due for r in self.recs])))
        self.log(f"ticks in window: {len(self.ticks)}")
        self.log(f"memory_peak_bytes: {self.memory_peak}")
        self.log(f"garbage collections in window by generation: "
                 f"{self.window_gcs}")
        self.log(f"setup_s: {self.setup_s}")

    # -- the check ------------------------------------------------------

    def check(self, control: bool = False) -> dict:
        """Compare a sample of the served answers, drawn from the seed,
        with the plain reference.  Frees the program's state first.

        Each answer is judged by the cell's kind: ``expected`` from the
        kind's ``reference`` over the configuration's sets, compared by
        ``same``.  With ``control`` the kind's ``control(ref, q)`` stands
        in the program's place: its answers for the same sample are
        compared instead of the served ones (``control.py``)."""
        from repro.serve import OK
        answered = [r for r in self.recs if r.ticket.done
                    and r.ticket.result.status == OK]
        pick = traffic_gen.stream_rng(self.seed, 5).permutation(
            len(answered))[:CHECK_SAMPLE]
        sample = [(answered[i].query, answered[i].ticket.result.value)
                  for i in sorted(pick)]
        self.server = self.index = self.arena = None
        self.recs = [dataclasses.replace(r, ticket=_Done(r.ticket))
                     for r in self.recs]
        t0 = time.monotonic()
        kind = self.kind
        ref = kind.reference(self.sets)
        wrong = 0
        for q, got in sample:
            if control:
                got = kind.control(ref, q)
            if not kind.same(got, kind.expected(ref, q)):
                wrong += 1
        self.log(f"reference check: {len(sample)} answers in "
                 f"{time.monotonic() - t0:.3f} s")
        s = self.summary()
        self.checked = len(sample)
        return {"wrong_answers": {"value": wrong, "limit": 0},
                "unanswered": {"value": s["lost"], "limit": 0}}

    # -- the result line --------------------------------------------------

    def metrics(self) -> dict:
        group = self.spec["per_layer"] if self.trace \
            else self.spec["end_to_end"]
        out = {}
        for m in group:
            value = reader(m["name"], self.base).read(self)
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
        return out


class _Done:
    """What the summary keeps of a ticket once the program is freed."""

    def __init__(self, ticket):
        self.done = ticket.done
        self.result = ticket.result
        self.telemetry = ticket.telemetry


def run(root: Path, workload: str, seed: int, seconds: float, trace: bool,
        *, t_start: float, chips: int | None = None, overrides=None,
        manifest=None, log=None, control: bool = False,
        base: Path = HERE) -> dict:
    """Run one cell and return its result line (a dict), with the
    compared numbers under ``checks``, last.  ``control``: see
    ``Run.check``; ``base``, ``chips``: see ``Run``."""
    r = Run(root, workload, seed, seconds, trace, t_start=t_start,
            overrides=overrides, manifest=manifest, log=log, base=base,
            chips=chips)
    r.setup()
    r.window()
    r.report()
    metrics = r.metrics()
    s = r.summary()
    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": r.chips,
              "memory_peak_bytes": r.memory_peak}
    breakdown = None
    if trace and r.reduced is not None:
        device["busy_s"] = r.reduced.busy_s
        device["window_s"] = r.reduced.window_s
        breakdown = r.reduced.breakdown()
    checks = r.check(control)
    correct = r.checked > 0 and all(c["value"] <= c["limit"]
                                    for c in checks.values())
    line = {"correct": correct, "attempted": len(r.recs),
            "failed": s["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    return line
