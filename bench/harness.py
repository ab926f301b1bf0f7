"""The benchmark's harness: resolves a cell from files, sets it up,
drives its window, reads its metrics and checks its outputs.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file the harness finds by the name ``BENCHMARK.json``
gives it:

* ``<config file>`` (from ``configs[].file``): sizes and ``kind``;
* ``bench/kinds/<kind>.py``: data from the seed, the system under test,
  the lower-precision control, the comparison with the reference, and
  ``describe(req)``: the small value a per-layer reader takes in place
  of a request (the window keeps that, not the request);
* ``bench/traffic/<traffic>.json``: the mix's parameters, read by the
  generator ``bench/traffic/<generator>.py`` that it names;
* ``bench/metrics/<metric>.py``: one reader per per-layer metric.

A run: make the data from the seed, build the system, send one request
of every shape the mix uses twice (set-up ends here), then drive a
closed loop -- one client, the next request sent when the previous
result is on the host -- for the window's seconds.  After the window:
read the device's memory peak, free the system, and compare a sample of
the window's results, drawn from the seed, with the reference.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
import traceback
from contextlib import nullcontext
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType

import numpy as np

ROOT = Path(__file__).resolve().parents[1]

# independent random streams drawn from one --seed
DATA, TRAFFIC, SAMPLE = 0, 1, 2


class BenchError(RuntimeError):
    """A run that cannot produce a result (exits nonzero)."""


def stream(seed: int, which: int) -> np.random.Generator:
    return np.random.default_rng([which, seed % (1 << 64)])


def load_module(path: Path) -> ModuleType:
    if not path.is_file():
        raise BenchError(f"missing file {path}")
    name = "bench_file_" + "_".join(path.with_suffix("").parts[-3:]).replace(
        ".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: Path) -> dict:
    if not path.is_file():
        raise BenchError(f"missing file {path}")
    return json.loads(path.read_text())


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    kind: ModuleType
    traffic: ModuleType
    end_to_end: list[dict]
    per_layer: list[dict]
    readers: dict[str, ModuleType] = field(default_factory=dict)


def resolve(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    bench = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    wl = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    config = _read_json(root / cfg["file"])
    mix = _read_json(root / "bench" / "traffic" / f"{wl['traffic']}.json")
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return Cell(
        name=name, chips=int(wl["chips"]), config=config, mix=mix,
        kind=load_module(root / "bench" / "kinds" / f"{config['kind']}.py"),
        traffic=load_module(
            root / "bench" / "traffic" / f"{mix['generator']}.py"),
        end_to_end=e2e, per_layer=per_layer,
        readers={m["name"]: load_module(
            root / "bench" / "metrics" / f"{m['name']}.py")
            for m in per_layer})


class CompileCounter:
    """Counts JAX's own compile events (tracing, lowering, backend
    compilation) while open."""

    PREFIX = "/jax/core/compile/"

    def __init__(self) -> None:
        import jax.monitoring

        self.events: list[str] = []
        self.open = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if self.open and event.startswith(self.PREFIX):
            self.events.append(event)


class Sampler:
    """A uniform sample of the window's results, ``per_kind`` of each
    kind of request, drawn from the seed (reservoir sampling)."""

    def __init__(self, per_kind: int, rng: np.random.Generator) -> None:
        self.per_kind = per_kind
        self.rng = rng
        self.seen: dict[str, int] = {}
        self.kept: dict[str, list] = {}

    def offer(self, kind: str, item) -> None:
        n = self.seen.get(kind, 0)
        self.seen[kind] = n + 1
        kept = self.kept.setdefault(kind, [])
        if n < self.per_kind:
            kept.append(item)
        else:
            j = int(self.rng.integers(0, n + 1))
            if j < self.per_kind:
                kept[j] = item

    @property
    def samples(self) -> list:
        return [s for k in sorted(self.kept) for s in self.kept[k]]


@dataclass(slots=True)
class Request:
    """One request of the window as the host saw it.  ``desc`` is the
    kind module's ``describe(req)``, never the request itself, so that
    the window's bookkeeping does not grow with the requests' payload."""
    i: int
    desc: object
    latency_s: float
    wallclock_ns: float | None
    failed: bool = False


@dataclass
class Window:
    """What a per-layer reader reads: the traced window's requests in
    order, the reduced trace, the cell and the device's peaks."""
    requests: list[Request]
    trace: object          # bench.trace.Trace
    lo: float              # traced window, trace clock (ns)
    hi: float
    cell: Cell
    device_kind: str

    def spans(self) -> list[tuple[Request, object]]:
        """(request, its span in the trace) for every traced request."""
        by_i = {r.i: r for r in self.requests}
        return [(by_i[self.trace.span_index(s)], s)
                for s in self.trace.spans if self.trace.span_index(s) in by_i]

    def peak(self, key: str) -> float:
        peaks = _read_json(ROOT / "bench" / "peaks.json")["devices"]
        if self.device_kind not in peaks:
            raise BenchError(f"no peaks for device kind {self.device_kind!r} "
                             "in bench/peaks.json")
        return float(peaks[self.device_kind][key])


def drive(system, requests, seconds: float, kind: ModuleType,
          sampler: Sampler, traced: bool) -> tuple[list[Request], float]:
    """The closed loop.  Returns the requests and the window's length:
    from its start to the end of the last request sent before its
    close.  ``kind`` is the cell's kind module; only the sampler keeps
    a request itself, with its result."""
    import jax

    out: list[Request] = []
    t_start = time.perf_counter()
    t_close = t_start + seconds
    t_end = t_start
    for i, req in enumerate(requests):
        span = (jax.profiler.TraceAnnotation("bench_request", i=i)
                if traced else nullcontext())
        t0 = time.perf_counter()
        if t0 >= t_close:
            break
        wall, failed = None, False
        with span:
            try:
                result, wall = system(req)
            except Exception:  # a failed request is counted, not fatal
                traceback.print_exc()
                failed = True
        t_end = time.perf_counter()
        out.append(Request(i, kind.describe(req), t_end - t0, wall, failed))
        if not failed:
            sampler.offer(kind.request_kind(req), (req, result))
    return out, t_end - t_start


def _percentile_ms(reqs: list[Request], q: float) -> float:
    return float(np.percentile([r.latency_s for r in reqs], q)) * 1e3


#: End-to-end metrics, each from the host clock around the window.
END_TO_END = {
    "requests_per_s": lambda reqs, span_s, peak, setup_s: len(reqs) / span_s,
    "latency_p50_ms": lambda reqs, span_s, peak, setup_s:
        _percentile_ms(reqs, 50),
    "latency_p95_ms": lambda reqs, span_s, peak, setup_s:
        _percentile_ms(reqs, 95),
    "device_peak_gib": lambda reqs, span_s, peak, setup_s: peak / (1 << 30),
    "setup_s": lambda reqs, span_s, peak, setup_s: setup_s,
}


def _memory_peak() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


def _compile_cache() -> str:
    import jax
    from repro.kernels.common import enable_compile_cache

    path = enable_compile_cache()
    # every program goes to the cache, however quick its compile, so
    # that only a checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        system: str = "program", t_origin: float | None = None) -> dict:
    """One run of ``cell``; returns the result line's object.
    ``system`` is ``program`` (the system under test) or ``control``
    (the reference one precision step down, in its place)."""
    import jax

    from bench import trace as tracing

    t_origin = time.perf_counter() if t_origin is None else t_origin
    _compile_cache()
    counter = CompileCounter()
    kind, traffic, config, mix = cell.kind, cell.traffic, cell.config, cell.mix
    data = kind.make_data(config, stream(seed, DATA))
    sut = {"program": kind.Program, "control": kind.Control}[system](
        config, data)
    for req in traffic.warm_requests(mix, config):
        sut(req)
        sut(req)
    setup_s = time.perf_counter() - t_origin

    sampler = Sampler(int(mix["check_per_kind"]), stream(seed, SAMPLE))
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        jax.profiler.start_trace(tdir)
    counter.open = True
    try:
        reqs, span_s = drive(sut, traffic.requests(mix, config,
                                                   stream(seed, TRAFFIC)),
                             seconds, kind, sampler, trace)
    finally:
        counter.open = False
        if trace:
            jax.profiler.stop_trace()
    if counter.events:
        if trace:
            shutil.rmtree(tdir, ignore_errors=True)
        raise BenchError(f"{len(counter.events)} compile events inside the "
                         f"window: {sorted(set(counter.events))}")
    memory_peak = _memory_peak()
    sut.close()
    del sut
    gc.collect()

    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev), "memory_peak_bytes": memory_peak}
    result: dict = {"correct": False, "attempted": len(reqs),
                    "failed": sum(r.failed for r in reqs), "metrics": {},
                    "device": device}
    if trace:
        try:
            path = next(Path(tdir).rglob("*.xplane.pb"))
            tr = tracing.load(str(path))
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        lo, hi = tracing.window(tr) or (0.0, 0.0)
        busy = tracing.busy_ns(tr, lo, hi)
        device["busy_s"] = (busy or 0.0) * 1e-9
        device["window_s"] = (hi - lo) * 1e-9
        w = Window(reqs, tr, lo, hi, cell, dev[0].device_kind)
        for m in cell.per_layer:
            value = cell.readers[m["name"]].read(w)
            if value is not None:
                result["metrics"][m["name"]] = {"value": float(value),
                                                "unit": m["unit"]}
        result["breakdown"] = tracing.breakdown(tr, lo, hi)
    else:
        done = [r for r in reqs if not r.failed]
        for m in cell.end_to_end:
            if m["name"] not in END_TO_END:
                raise BenchError(f"no way to take end-to-end metric "
                                 f"{m['name']!r}")
            value = END_TO_END[m["name"]](done or reqs, span_s,
                                          memory_peak, setup_s)
            result["metrics"][m["name"]] = {"value": float(value),
                                            "unit": m["unit"]}

    try:
        numbers = kind.check(config, data, sampler.samples)
    except Exception:
        traceback.print_exc()
        numbers = None
    checks = {}
    ok = numbers is not None and bool(sampler.samples)
    for name, value in (numbers or {}).items():
        limit = kind.LIMITS[name]
        checks[name] = {"value": value, "limit": limit}
        ok = ok and value <= limit          # NaN compares false: fails
    result["correct"] = ok and result["failed"] == 0
    result["checks"] = checks
    return result


def report(result: dict) -> None:
    """Each compared number beside its limit as the last lines on
    standard error, then the result as the last line on standard
    output."""
    sys.stdout.flush()
    checks = result.get("checks", {})
    print(f"checked: attempted {result['attempted']}, failed "
          f"{result['failed']}, correct {result['correct']}",
          file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    for c in checks.values():   # JSON has no infinity or NaN
        if not math.isfinite(c["value"]):
            c["value"] = str(c["value"])
    print(json.dumps(result))
