"""The harness: one run of one cell.

It finds everything by name: the cell in ``BENCHMARK.json``, its
configuration file, its traffic mix (``traffic/<mix>.json``, read by
``traffic/generator.py``, which finds the configuration's document
shape in ``traffic/shapes/`` and each of the mix's loads in
``traffic/loads/``) and one reader per metric (``metrics/<name>.py``,
or ``metrics/<base>.py`` for ``<base>.<tag>``).  A later PR adds a
cell, a shape, a load or a metric by adding files and entries.

A run: check the device, make the logs from the seed, start the
server in this process (``service.make_server(port=0)``, as
``python -m crdt_graph_tpu.service`` starts it), drive the warm-up
shapes, measure the window, read the answers back over HTTP, stop the
server, and compare the answers with the plain reference
(``reference.py``).  With ``--trace 1`` the window runs under the JAX
profiler and the per-layer metrics are read; otherwise the
end-to-end ones.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

sys.path.insert(0, ROOT)
from benchmark import reference, trace_reduce  # noqa: E402
from benchmark.traffic import generator  # noqa: E402


class NoDevice(SystemExit):
    pass


def load_spec() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find(items: list, name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise SystemExit(f"no {what} named {name!r} in BENCHMARK.json")


def load_config(spec: dict, name: str) -> dict:
    entry = find(spec["configs"], name, "configuration")
    with open(os.path.join(ROOT, entry["file"])) as f:
        return json.load(f)


def load_reader(name: str):
    """The module that reads metric ``name``: ``metrics/<name>.py``,
    else ``metrics/<base>.py`` for ``<base>.<tag>``."""
    for stem in (name, name.split(".")[0]):
        path = os.path.join(HERE, "metrics", f"{stem}.py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                f"benchmark_metric_{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod
    raise SystemExit(f"no reader for metric {name!r} under metrics/")


def metrics_for(spec: dict, cell: str, trace: bool) -> List[dict]:
    """The metrics this cell reports: its end-to-end ones (``--trace
    0``) or its per-layer ones (``--trace 1``).  A metric with a
    ``workloads`` list is reported in those cells; a per-layer metric
    without one in every cell that reports the metric it moves."""
    e2e = [m for m in spec["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


# -- the device ----------------------------------------------------------------

def device_info(chips: int, allow_cpu: bool = False) -> dict:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" and not allow_cpu:
        raise NoDevice(f"benchmark: no TPU (JAX found "
                       f"{devs[0].platform} x{len(devs)}); no result")
    if len(devs) < chips:
        raise NoDevice(f"benchmark: the cell asks for {chips} chips, "
                       f"JAX found {len(devs)}; no result")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


class CompileCounter:
    """Counts JAX lowerings (each new jit specialisation, cache hit or
    not) while armed."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax
        self.n = 0
        self.armed = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if self.armed and event == self.EVENT:
            self.n += 1


# -- the server ----------------------------------------------------------------

class Server:
    """The system under test, in this process, and the few handles the
    generator needs besides HTTP: hold the scheduler, see queues, and
    drop a document it has checked or will not check."""

    def __init__(self):
        from crdt_graph_tpu.service import make_server
        self.srv = make_server(port=0)
        self.engine = self.srv.store
        self.port = self.srv.server_port
        self.thread = threading.Thread(target=self.srv.serve_forever,
                                       name="bench-http", daemon=True)
        self.thread.start()

    def pause(self) -> None:
        self.engine.scheduler.pause()

    def resume(self) -> None:
        self.engine.scheduler.resume()

    def wait_queued(self, docs, timeout: float) -> None:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if all((d := self.engine.get(doc, create=False)) is not None
                   and len(d.queue) > 0 for doc in docs):
                return
            time.sleep(0.005)
        raise RuntimeError(f"documents {docs} never queued")

    def drop(self, doc_id: str) -> None:
        with self.engine._lock:
            self.engine._docs.pop(doc_id, None)

    def get(self, path: str) -> bytes:
        status, body = generator.Client(self.port).request("GET", path)
        if status != 200:
            raise RuntimeError(f"GET {path} -> {status}")
        return body

    def stop(self) -> None:
        self.srv.shutdown()
        self.srv.server_close()
        self.engine.scheduler.join(60)
        self.thread.join(60)


# -- the run -------------------------------------------------------------------

@dataclasses.dataclass
class Ctx:
    """What a metric reader reads."""
    cell: dict
    config: dict
    traffic: str
    window_s: float
    setup_s: float
    writes: list
    reads: list
    spans: Dict[str, dict]
    counters: Dict[str, int]
    commits: List[dict]
    compiles: int
    trace: Optional[dict]
    device: dict

    @property
    def acked_writes(self) -> list:
        return [r for r in self.writes if r.ok]

    @property
    def acked_ops(self) -> int:
        return sum(r.n_ops for r in self.acked_writes)


def _span_delta(before: dict, after: dict) -> dict:
    out = {}
    for k, v in after.items():
        b = before.get(k, {"count": 0, "total_ms": 0.0})
        n = v["count"] - b["count"]
        if n:
            out[k] = {"count": n, "total_ms": v["total_ms"] - b["total_ms"]}
    return out


def run(workload: str, seed: int, seconds: float, trace: bool,
        control: bool = False, allow_cpu: bool = False,
        overrides: Optional[dict] = None,
        t_start: Optional[float] = None) -> dict:
    """One run; returns the result line as a dict."""
    t_start = time.perf_counter() if t_start is None else t_start
    spec = load_spec()
    cell = find(spec["workloads"], workload, "workload")
    config = load_config(spec, cell["config"])
    config.update(overrides or {})
    mix = generator.load_mix(cell["traffic"])
    metrics = metrics_for(spec, workload, trace)

    import jax
    from crdt_graph_tpu.utils import compcache, profiling
    compcache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_enable_x64", True)
    phases: Dict[str, float] = {}

    def mark(name: str) -> None:
        phases[name] = time.perf_counter() - t_start

    device = device_info(cell["chips"], allow_cpu)
    compiles = CompileCounter()
    mark("jax")
    traffic = generator.Traffic(mix, config, seed)
    mark("logs")
    server = Server()
    mark("server")
    records: List[dict] = []
    trace_dir = None
    try:
        traffic.setup(server, mark)
        # the warm-up's commits finish (and are recorded) before the
        # window: an ack can precede its commit's flight record
        server.engine.flush(120)
        gc.collect()
        spans0 = profiling.span_stats()
        counters0 = server.engine.counters.snapshot()
        wall0 = time.time()
        if trace:
            server.engine.flight.add_listener(records.append)
            trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            trace_reduce.start(trace_dir)
        compiles.armed = True
        setup_s = time.perf_counter() - t_start
        mark("flush")
        print("bench: set-up s by phase (cumulative) " + ", ".join(
            f"{k} {v:.3f}" for k, v in phases.items()), file=sys.stderr)
        t0, t1 = traffic.run(server, seconds)
        compiles.armed = False
        if trace:
            trace_reduce.stop()
            server.engine.flight.remove_listener(records.append)
        spans = _span_delta(spans0, profiling.span_stats())
        counters = {k: v - counters0.get(k, 0) for k, v in
                    server.engine.counters.snapshot().items()}
        device["memory_peak_bytes"] = memory_peak_bytes()
        answers = read_answers(server, traffic)
        spilled = _dir_bytes(server.engine.oplog_dir)
    finally:
        server.stop()
    del server
    gc.collect()

    in_window = [r for r in traffic.reqs if r.t_sent >= t0]
    writes = [r for r in in_window if r.kind != "read"]
    reads = [r for r in in_window if r.kind == "read"]
    tsum = None
    if trace:
        tsum = trace_reduce.summarize(trace_dir, records)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = tsum["busy_s"]
        device["window_s"] = tsum["window_s"]
    records = [r for r in records if r["ts"] >= wall0]
    ctx = Ctx(cell=cell, config=config, traffic=cell["traffic"],
              window_s=t1 - t0, setup_s=setup_s, writes=writes,
              reads=reads, spans=spans, counters=counters,
              commits=records, compiles=compiles.n, trace=tsum,
              device=device)
    out_metrics = {}
    for m in metrics:
        v = load_reader(m["name"]).read(ctx)
        if v is not None:
            out_metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    checks = compare(traffic, answers, control)
    failed = sum(1 for r in in_window if not r.ok)
    correct = failed == 0 and all(c["ok"] for c in checks.values())
    result = {"correct": correct, "attempted": len(in_window),
              "failed": failed, "metrics": out_metrics, "device": device}
    if tsum is not None:
        result["breakdown"] = tsum["breakdown"]
    result["checks"] = checks
    print(f"bench: host max RSS {_max_rss_mb():.1f} MB, op-log spill "
          f"dir {spilled} bytes at the end, docs checked "
          f"{len(traffic.checked)}", file=sys.stderr)
    bulk = [round((r.t_done - r.t_due) * 1e3, 1) for r in writes
            if r.kind == "bulk"]
    if bulk:
        print(f"bench: bulk write latencies ms {bulk}", file=sys.stderr)
    late = traffic.lateness_ms
    if late:
        print(f"bench: open-loop pushes {len(late)}, lateness ms "
              f"max {max(late):.3f} mean {sum(late) / len(late):.3f}",
              file=sys.stderr)
    return result


def _max_rss_mb() -> float:
    import resource
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024


def _dir_bytes(path: Optional[str]) -> int:
    total = 0
    for d, _, files in os.walk(path or ""):
        for f in files:
            try:
                total += os.path.getsize(os.path.join(d, f))
            except OSError:
                pass
    return total


# -- the check -----------------------------------------------------------------

def read_answers(server: Server, traffic) -> dict:
    """What the program says, read over HTTP once the window closed:
    every document the traffic named for the check."""
    out = {}
    for doc in sorted(traffic.checked):
        values = json.loads(server.get(f"/docs/{doc}"))["values"]
        ops = server.get(f"/docs/{doc}/ops?since=0")
        out[doc] = (values, ops)
    return out


def compare(traffic, answers: dict, control: bool = False) -> dict:
    """The program's answers against the reference.  With ``control``
    the values come from the control instead of the program."""
    n_values = n_ops = 0
    ref_cache: dict = {}
    deltas: Dict[str, list] = {}
    for r in traffic.reqs:
        if r.kind == "delta" and r.ok:
            deltas.setdefault(r.doc, []).extend(r.ops)
    for doc, (values, body) in sorted(answers.items()):
        li = traffic.checked[doc]
        log = traffic.logs[li]
        extra = deltas.get(doc, [])
        if log.cols is not None and not extra:
            want = log.expected
            if control:
                per = traffic.config["ops"] // traffic.config["replicas"]
                values = reference.chain_control_values(log.cols, per)
            n_ops += reference.opset_mismatches_chain(body, log.cols)
        else:
            ops = log.ops + extra
            key = (li, len(extra), doc if extra else None)
            if key not in ref_cache:
                ref_cache[key] = reference.visible_values(ops)
            want = ref_cache[key]
            if control:
                values = reference.control_values(ops)
            n_ops += reference.opset_mismatches(body, ops)
        n_values += reference.value_mismatches(values, want)
    wrong_acks = sum(1 for r in traffic.reqs
                     if r.kind != "read" and r.status == 200 and r.error)
    ryw = sum(1 for r in traffic.reqs if r.kind == "read" and not r.ryw)
    checks = {"docs_checked": {"value": len(answers), "limit": ">=1",
                               "ok": len(answers) >= 1}}
    for name, v in (("value_mismatches", n_values),
                    ("opset_mismatches", n_ops),
                    ("wrong_acks", wrong_acks), ("ryw_misses", ryw)):
        checks[name] = {"value": v, "limit": "<=0", "ok": v <= 0}
    return checks
