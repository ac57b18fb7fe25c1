"""One run of one cell: set-up, a closed-loop window, the check, the metrics.

The system under test is the program under ``src/``: a cell's ``layout``
names its entry, the file ``bench/entries/<layout>.py`` (``spec``);
``single`` drives ``repro.search.subsequence_search`` on one chip. The
deployment's shape (``ref_len``, ``query_len``, ``window_ratio``) comes
from the configuration file; every tuning knob from the program's own
``repro.configs.dtw_search.CONFIG``, so a PR that retunes the program is
measured as its users get it.

One client issues queries back to back and waits for each answer
(``block_until_ready`` and a fetch of the result) before it sends the next.
New queries start only while fewer than ``seconds`` have passed; the window
ends when the last one completes.

A traced run records the first ``TRACE_SECONDS`` of the window only, and
reads the per-layer metrics from the queries sent in that slice. The
profiler keeps one event per device op, and the search runs one op per
window in the LB cascade's gather and a dozen per round: on the v5e a
second of queries makes from 300,000 (l=1024) to over a million (l=128)
events. Past about six million the profiler drops events ("Trace Buffers
Dropped"), and reading a whole window's trace takes minutes.
"""
from __future__ import annotations

import gc
import shutil
import tempfile
import time
import traceback
from dataclasses import dataclass

import jax

from benchkit import check, spec, traffic
from benchkit import trace as tr

TRACE_SECONDS = 2.0


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclass(frozen=True)
class QueryRecord:
    pool_index: int
    latency_s: float
    best_start: int
    best_dist: float
    rounds: int
    lb_pruned: int


@dataclass
class Run:
    """What a metric reader reads: the cell, its answers, its trace."""
    cell: spec.Cell
    n_windows: int                 # candidate windows per query
    queries: list                  # ``QueryRecord`` of every answer
    window_s: float                # first dispatch to last completion
    setup_s: float
    trace: tr.Trace | None = None
    traced: list | None = None     # ``QueryRecord`` of the traced slice


def require_chip(chips: int) -> list:
    """The first ``chips`` TPU devices; raises ``NoChip`` otherwise."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def device_line(devs) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(max(peaks))}


def knobs(cell: spec.Cell) -> dict:
    """The deployment's shape from the config, the tuning from the program."""
    from repro.configs.dtw_search import CONFIG

    length = int(cell.config["query_len"])
    return dict(
        length=length,
        window=int(length * float(cell.config["window_ratio"])),
        batch=CONFIG.batch, band_width=CONFIG.band_width,
        block_k=CONFIG.block_k, row_block=CONFIG.row_block,
        quarantine=CONFIG.quarantine,
    )


class CompileCounter:
    """Counts JAX trace, lowering and compile events while ``on``."""

    def __init__(self):
        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **kw) -> None:
        if self.on and event.startswith("/jax/core/compile/"):
            self.count += 1

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._event)


class Client:
    """One closed-loop client: sends a query, waits for its answer, sends
    the next."""

    def __init__(self, entry, wl: traffic.Workload):
        self.entry, self.wl = entry, wl
        self.records, self.failed, self.sent = [], 0, 0
        self.t0 = time.perf_counter()

    def run_until(self, seconds: float) -> None:
        """Send queries while fewer than ``seconds`` have passed since the
        client started."""
        span = jax.profiler.TraceAnnotation
        while True:
            with span("bench.next"):
                if time.perf_counter() - self.t0 >= seconds:
                    return
                p = self.wl.query_index(self.sent)
                q = self.wl.pool[p]
                self.sent += 1
            ts = time.perf_counter()
            try:
                with span("bench.dispatch"):
                    out = self.entry.dispatch(q)
                with span("bench.wait"):
                    jax.block_until_ready(out)
                with span("bench.fetch"):
                    ans = self.entry.fetch(out)
            except Exception:  # a failed query is counted, not fatal
                traceback.print_exc()
                self.failed += 1
                continue
            self.records.append(
                QueryRecord(p, time.perf_counter() - ts, *ans))


def closed_loop(entry, wl: traffic.Workload, seconds: float,
                trace_dir: str | None = None):
    """Queries back to back for ``seconds``; returns records, the number
    sent, the number failed, the window's seconds and, where ``trace_dir``
    is given, the records of the traced slice (the first
    ``TRACE_SECONDS``)."""
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    client = Client(entry, wl)
    traced = None
    if trace_dir:
        try:
            with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
                client.run_until(min(TRACE_SECONDS, seconds))
        finally:
            jax.profiler.stop_trace()
        traced = list(client.records)
    client.run_until(seconds)
    return (client.records, client.sent, client.failed,
            time.perf_counter() - client.t0, traced)


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             root=spec.ROOT, t_start: float | None = None) -> dict:
    """One run of cell ``name``; returns the result line's object.

    Raises ``NoChip`` before any work where the chips are missing.
    """
    t_start = time.perf_counter() if t_start is None else t_start
    cell = spec.load_cell(name, root)
    Entry = spec.entry_class(cell.config["layout"], root)
    devs = require_chip(cell.chips)
    t_chip = time.perf_counter()
    from repro.core.backend import resolve_backend

    if devs[0].platform == "tpu" and resolve_backend() != "pallas":
        raise NoChip(f"the DTW backend resolved to {resolve_backend()!r}, "
                     "not 'pallas'")
    kn = knobs(cell)
    wl = traffic.build(cell.config, cell.traffic, seed)
    entry = Entry(cell, wl.ref, devs)
    t_data = time.perf_counter()
    # Set-up: this cell's own program, once; with the persistent cache
    # warm it only loads.
    entry.fetch(jax.block_until_ready(entry.dispatch(wl.pool[0])))
    t_warm = time.perf_counter()
    setup_s = t_warm - t_start

    compiles = CompileCounter()
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        compiles.on = True
        records, attempted, failed, window_s, traced = closed_loop(
            entry, wl, seconds, trace_dir)
        compiles.on = False
        device = device_line(devs)
        tr_summary = (tr.load(trace_dir, [d.id for d in devs])
                      if trace else None)
    finally:
        compiles.close()
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    if compiles.count:
        raise RuntimeError(f"{compiles.count} JAX compilation events inside "
                           "the measured window: set-up missed a shape")

    # The program's state goes before the reference uses the chip.
    del entry
    gc.collect()
    n_win = int(cell.config["ref_len"]) - kn["length"] + 1
    run = Run(cell=cell, n_windows=n_win, queries=records,
              window_s=window_s, setup_s=setup_s, trace=tr_summary,
              traced=traced)

    refc = check.Reference(wl.ref, wl.pool, kn["length"], kn["window"],
                           wl.offsets)
    answers = [check.Answer(r.pool_index, r.best_start, r.best_dist)
               for r in records]
    if not answers:
        raise RuntimeError("no query was answered in the window")
    t_ref = time.perf_counter()
    expected = refc.nearest([a.pool_index for a in answers], devices=devs)
    reference_s = time.perf_counter() - t_ref
    numbers = refc.compare(answers, expected)
    limits = cell.config["limits"]
    correct = not failed and check.verdict(numbers, limits)

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = spec.metric_reader(m["name"], root)(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if trace:
        device["busy_s"] = tr.busy_ns(tr_summary) / 1e9
        device["window_s"] = tr_summary.window_ns / 1e9
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = {"device_ops": tr.top_ops(tr_summary),
                               "idle_gaps": tr.idle_by_host_span(tr_summary)}
    info = dict(queries=len(records), window_s=window_s, setup_s=setup_s,
                setup_to_chip_s=t_chip - t_start,
                setup_data_s=t_data - t_chip, setup_warm_s=t_warm - t_data,
                distinct_queries=len(expected), reference_s=reference_s,
                **refc.diagnostics(answers, expected))
    if trace:
        info.update(traced_queries=len(traced),
                    trace_dropped_s=tr_summary.dropped_ns / 1e9)
    result["checks"] = check.report(numbers, limits, info)
    return result
