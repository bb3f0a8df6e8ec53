#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` → ``workloads``) names a configuration
(``configs/<name>.json``: data scale, coordinator and planner settings,
the limits of the comparison) and a traffic mix (``traffic/<name>.json``:
clients and the queries each one sends, found as ``queries/<q>.sql`` with
the plain reference ``queries/<q>.py``). Every metric is read by
``metrics/<name>.py``. Nothing here names a cell, a configuration, a mix
or a metric.

Set-up makes TPC-H data from ``--seed``, loads it into the system's
object store, and runs the cell's own traffic until a whole pass compiles
nothing. The window then drives the client entry point (``connect`` →
``session.submit`` → the handle's rows) for ``--seconds`` and keeps only
timestamps and the rows. After it, the rows are compared with the
reference on the same data. The last line of standard output is the
result JSON; the numbers compared, each beside its limit, are the last
lines of standard error. One line per query goes to
``bench_out/<workload>.jsonl``.

Exits 2, printing no result, when JAX's devices are not TPUs or fewer
than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
OUT_DIR = ROOT / "bench_out"
sys.path.insert(0, str(HERE))

import numpy as np  # noqa: E402

import compare  # noqa: E402
import tpch_data  # noqa: E402

# A warm-up pass that still compiles is repeated, up to this many passes.
MAX_WARM_PASSES = 12


def _module(path: Path):
    name = "bench_" + "".join(ch if ch.isalnum() else "_"
                              for ch in path.as_posix())
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """A workload of ``BENCHMARK.json`` with its configuration, traffic,
    queries and metric readers, all found by name."""

    def __init__(self, workload: str, here: Path = HERE):
        self.bench = json.loads(
            (here.parents[1] / "BENCHMARK.json").read_text())
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
        self.name = workload
        self.entry = cells[workload]
        cfg = {c["name"]: c for c in self.bench["configs"]}[
            self.entry["config"]]
        self.here = here
        self.config = json.loads((here.parents[1] / cfg["file"]).read_text())
        self.traffic = json.loads(
            (here / "traffic" / f"{self.entry['traffic']}.json").read_text())
        self.queries = sorted({q for c in self.traffic["clients"]
                               for q in c["queries"]})
        self.sql = {q: (here / "queries" / f"{q}.sql").read_text()
                    for q in self.queries}

    def reference(self, query: str):
        return _module(self.here / "queries" / f"{query}.py").reference

    def metrics(self, kind: str) -> list[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
        return [m for m in self.bench[kind]
                if self.name in m.get("workloads", [self.name])]

    def reader(self, metric: str):
        """``metrics/<name>.py``; where there is none, the reader of the
        name before its first dot, so that ``latency_p90_s.join`` reads
        as ``latency_p90_s`` does."""
        path = self.here / "metrics" / f"{metric}.py"
        if not path.exists():
            path = self.here / "metrics" / f"{metric.split('.', 1)[0]}.py"
        return _module(path).read

    def sequences(self, seed: int) -> list[list[str]]:
        """Each client's cycle of queries; the seed picks where each
        starts, so every seed sends the same queries in another order."""
        out = []
        for i, c in enumerate(self.traffic["clients"]):
            qs = c["queries"]
            k = (seed + i) % len(qs)
            out.append(qs[k:] + qs[:k])
        return out


class Recorder:
    """Host-clock records of the session's observer events."""

    def __init__(self):
        self.events: list[tuple[int, str, str, str]] = []

    def on_query_state(self, qid, state):
        self.events.append((time.perf_counter_ns(), qid, "state", state))

    def on_pipeline_start(self, qid, pid, sem_hash, n_fragments):
        self.events.append((time.perf_counter_ns(), qid, "start", str(pid)))

    def on_pipeline_complete(self, qid, report):
        kernel = ",".join(sorted(report.kernel_runs)) or "generic"
        self.events.append((time.perf_counter_ns(), qid, "complete",
                            f"{report.pid}:{kernel}"))

    def __getattr__(self, name):          # the observer's other hooks
        if name.startswith("on_"):
            return lambda *a, **k: None
        raise AttributeError(name)


class CompileCounter:
    """Programs JAX compiles or loads from its persistent cache."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.count += 1


def drive(session, sequences, sql, compiles, *, deadline=None,
          passes=None):
    """Run closed-loop clients until ``deadline`` (a perf_counter time;
    a query started before it runs to its end) or for ``passes`` cycles.
    Returns one record per query; the window does nothing else."""
    records: list[dict] = []
    store = session.store

    def client(i, seq):
        n = 0
        while True:
            q = seq[n % len(seq)]
            if passes is not None and n >= passes * len(seq):
                return
            t0 = time.perf_counter()
            if deadline is not None and t0 >= deadline:
                return
            c0, r0, w0 = compiles.count, store.stats.bytes_read, \
                store.stats.bytes_written
            handle = session.submit(sql[q])
            rows, error = None, None
            try:
                rows = handle.fetch()
            except Exception as e:  # noqa: BLE001 - a failed query counts
                error = f"{type(e).__name__}: {e}"
            t1 = time.perf_counter()
            records.append({
                "query": q, "client": i, "t0": t0, "t1": t1,
                "compiles": compiles.count - c0,
                "bytes_read": store.stats.bytes_read - r0,
                "bytes_written": store.stats.bytes_written - w0,
                "handle": handle, "rows": rows, "error": error})
            n += 1

    threads = [threading.Thread(target=client, args=(i, s), daemon=True)
               for i, s in enumerate(sequences)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return sorted(records, key=lambda r: r["t0"])


def connect_cell(cell: Cell):
    from repro.api import connect
    from repro.core import CoordinatorConfig
    from repro.sql.physical import PlannerConfig
    cfg = cell.config
    return connect(config=CoordinatorConfig(
        planner=PlannerConfig(**cfg["planner"]), **cfg["coordinator"]))


class Run:
    """Everything a metric reader may read about one run."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    @property
    def latencies(self) -> list[float]:
        return [r["t1"] - r["t0"] for r in self.records if not r["error"]]

    def latency_quantile(self, tenths: int) -> float | None:
        """The ``tenths``/10 quantile of every completed query's latency,
        where at least ten queries came."""
        lat = self.latencies
        if len(lat) < 10:
            return None
        return statistics.quantiles(lat, n=10, method="inclusive")[tenths - 1]

    def kernel_roofline_pct(self, kernel: str) -> float | None:
        """A kernel's roofline time over its traced time, in percent."""
        if not self.trace or not self.trace["kernel_s"].get(kernel):
            return None
        return 100.0 * self.trace["kernel_roofline_s"][kernel] \
            / self.trace["kernel_s"][kernel]


def _enrich(records, recorder: Recorder) -> None:
    """Per-query counts and host-clock phases, read after the window from
    the handles and the observer's events."""
    events: dict[str, list] = {}
    for t, qid, kind, detail in recorder.events:
        events.setdefault(qid, []).append((t, kind, detail))
    for r in records:
        h = r["handle"]
        t0 = r["t0"] * 1e9
        states, opened, pipes = {}, {}, []
        for t, kind, detail in events.get(h.query_id, []):
            if kind == "state":
                states[detail] = t
            elif kind == "start":
                opened[detail] = t
            elif kind == "complete":
                pid, kernel = detail.split(":", 1)
                if pid in opened:
                    pipes.append((int(pid), kernel, opened.pop(pid), t))
        r["states"], r["spans"] = states, sorted(pipes)
        if "PLANNING" in states and "RUNNING" in states:
            r["plan_ms"] = (states["RUNNING"] - states["PLANNING"]) / 1e6
            r["queue_ms"] = (states["PLANNING"] - t0) / 1e6
        if "SUCCEEDED" in states:
            r["fetch_ms"] = r["t1"] * 1e3 - states["SUCCEEDED"] / 1e6
        # (pipeline, kernel, start and length in ms from the submit)
        r["pipelines"] = [[pid, k, round((s - t0) / 1e6, 3),
                           round((e - s) / 1e6, 3)]
                          for pid, k, s, e in r["spans"]]
        if r["error"]:
            continue
        reports = [p for p in h.stats().pipelines if not p.cache_hit]
        r["fragments"] = sum(p.n_fragments for p in reports)
        r["kernel_fragments"] = sum(p.kernel_fragments for p in reports)
        r["stragglers"] = sum(p.stragglers_retriggered for p in reports)
        r["attempts"] = sum(p.attempts for p in reports)
        r["topups"] = sum(p.topups for p in reports)
        r["kernels"] = sorted({k for p in reports for k in p.kernel_runs})


def _phases(records):
    """On the host clock in ns: (name, start, end) of each query, its
    planning, its pipelines with their kernels and its final fetch; and
    (start, end, kernel) of every pipeline that ran a fused kernel."""
    phases, kernels = [], []
    for r in records:
        q, st, t1 = r["query"], r["states"], r["t1"] * 1e9
        phases.append((f"{q}:query", r["t0"] * 1e9, t1))
        if "PLANNING" in st and "RUNNING" in st:
            phases.append((f"{q}:planning", st["PLANNING"], st["RUNNING"]))
        for pid, kernel, s, e in r["spans"]:
            phases.append((f"{q}:pipeline {pid} {kernel}", s, e))
            if kernel != "generic":
                kernels.append((s, e, kernel))
        done = st.get("SUCCEEDED", st.get("FAILED"))
        if done is not None:
            phases.append((f"{q}:final fetch", done, t1))
    return phases, kernels


def _reduce_trace(workload, trace_dir, anchor_host_ns, records, w0, w1,
                  device_kind) -> dict | None:
    """The window's trace reduced to metrics (None without the anchor);
    a small excerpt with each kernel's times goes to the output dir."""
    import tracereduce as tr
    planes = tr.load(trace_dir)
    anchor = tr.anchor_ns(planes)
    if anchor is None:
        return None
    off = anchor - anchor_host_ns
    phases, spans = _phases(records)
    summary = tr.reduce(
        tr.device_ops(planes), w0 * 1e9 + off, w1 * 1e9 + off,
        [(n, s + off, e + off) for n, s, e in phases],
        [(s + off, e + off, k) for s, e, k in spans], device_kind)
    doc = tr.excerpt(planes)
    doc["kernels"] = {k: {"calls": summary["kernel_calls"][k],
                          "traced_s": summary["kernel_s"][k],
                          "hbm_roofline_s": summary["kernel_roofline_s"][k]}
                      for k in summary.get("kernel_s", {})}
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    (OUT_DIR / f"{workload}.trace_excerpt.json").write_text(json.dumps(doc))
    return summary


def device_record(chips: int) -> dict:
    import jax
    devs = jax.devices()[:chips]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             chips: int, log=print) -> dict:
    """One run: set-up, the window, the comparison. Returns the result."""
    import jax
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    if jax.default_backend() == "tpu":
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = CompileCounter()
    recorder = Recorder()

    t = time.perf_counter()
    cfg = cell.config
    data = tpch_data.generate(cfg["scale_factor"], seed)
    session = connect_cell(cell)
    session.add_observer(recorder)
    try:
        session.attach_catalog(tpch_data.load(
            session.store, data, cfg["scale_factor"],
            cfg["row_group_rows"]))
        load_s = time.perf_counter() - t

        sequences = cell.sequences(seed)
        t = time.perf_counter()
        warm_passes = []
        for _ in range(MAX_WARM_PASSES):
            c0 = compiles.count
            drive(session, sequences, cell.sql, compiles, passes=1)
            warm_passes.append(compiles.count - c0)
            if len(warm_passes) >= 2 and warm_passes[-1] == 0:
                break
        warm_s = time.perf_counter() - t
        log(f"set-up: load {load_s:.2f}s, warm-up {warm_s:.2f}s, "
            f"compiles per pass {warm_passes}")

        recorder.events.clear()
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced \
            else None
        if traced:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0   # no per-call Python events
            opts.host_tracer_level = 1     # annotations only
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            from tracereduce import ANCHOR
            with jax.profiler.TraceAnnotation(ANCHOR):
                anchor_host_ns = time.perf_counter_ns()
        # the set-up's garbage goes now, and what survives it is never
        # scanned again by the collector inside the window
        gc.collect()
        gc.freeze()
        store0 = (session.store.stats.bytes_read,
                  session.store.stats.bytes_written)
        c0 = compiles.count
        w0 = time.perf_counter()
        setup_s = w0 - T_START
        records = drive(session, sequences, cell.sql, compiles,
                        deadline=w0 + seconds)
        w1 = max((r["t1"] for r in records), default=w0)
        window_compiles = compiles.count - c0
        store1 = (session.store.stats.bytes_read,
                  session.store.stats.bytes_written)
        if traced:
            jax.profiler.stop_trace()
        device = device_record(chips)
        _enrich(records, recorder)

        summary = None
        if traced:
            summary = _reduce_trace(cell.name, trace_dir, anchor_host_ns,
                                    records, w0, w1, device["kind"])
            shutil.rmtree(trace_dir, ignore_errors=True)
            if summary:
                device["busy_s"] = summary["busy_s"]
                device["window_s"] = summary["window_s"]
    finally:
        gc.unfreeze()
        session.close()

    # the plain reference, once per query, on the same data
    ok = [r for r in records if not r["error"]]
    refs = {q: cell.reference(q)(data, np.float64)
            for q in sorted({r["query"] for r in ok})}
    checks = compare.judge([(r["query"], r["rows"]) for r in ok], refs,
                           len(records) - len(ok), cfg["limits"])

    run = Run(records=records, window_s=w1 - w0, n=len(ok),
              setup={"setup_s": setup_s, "load_s": load_s,
                     "warm_s": warm_s, "warm_passes": warm_passes},
              window_compiles=window_compiles,
              bytes_read=store1[0] - store0[0],
              bytes_written=store1[1] - store0[1],
              trace=summary, device=device)
    kind = "per_layer" if traced else "end_to_end"
    metrics = {}
    for m in cell.metrics(kind):
        v = cell.reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    _write_records(cell.name, seed, traced, records, w0)
    result = {"correct": compare.passed(checks), "attempted": len(records),
              "failed": len(records) - len(ok), "metrics": metrics,
              "device": device}
    if summary:
        ops = sorted(summary["op_s"].items(), key=lambda kv: -kv[1])[:10]
        result["breakdown"] = {"device_ops": [list(o) for o in ops],
                               "idle_gaps": [list(g) for g in
                                             summary["idle_gaps"]]}
    result["checks"] = checks
    return result


def _write_records(workload, seed, traced, records, w0) -> None:
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    keep = ("query", "client", "compiles", "bytes_read", "bytes_written",
            "queue_ms", "plan_ms", "fetch_ms", "pipelines", "fragments",
            "kernel_fragments", "stragglers", "attempts", "topups",
            "kernels", "error")
    with open(OUT_DIR / f"{workload}.jsonl", "a") as f:
        for r in records:
            line = {"pid": os.getpid(), "seed": seed, "trace": int(traced),
                    "start_s": r["t0"] - w0, "latency_s": r["t1"] - r["t0"]}
            line.update({k: r[k] for k in keep if r.get(k) is not None})
            f.write(json.dumps(line) + "\n")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = Cell(args.workload)
    chips = int(cell.entry["chips"])
    import jax
    devs = jax.devices()
    print(f"device: platform={devs[0].platform} "
          f"kind={devs[0].device_kind} count={len(devs)}", flush=True)
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"run.py: needs {chips} TPU chip(s), JAX has {len(devs)} "
              f"{devs[0].platform} device(s)", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    result = run_cell(cell, args.seed % 2**63, args.seconds,
                      bool(args.trace), chips)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})"
              f"{' FAIL' if c['value'] > c['limit'] else ''}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
