"""The benchmark harness: one run of one cell.

Everything that belongs to one configuration, traffic mix or metric is a
file found by name (see README.md): the harness reads `BENCHMARK.json`,
loads the cell's configuration and traffic, builds the cell through the
entry its traffic names, warms it, measures a window of `--seconds`,
checks the window's answers against the plain reference, and prints the
result line.
"""

from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import math
import resource
import sys
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
CACHE_DIR = ROOT / ".jax_cache"
#: a traced run measures at most this long: the trace of a longer window
#: costs more to write and read than it adds to per-layer numbers
TRACE_SECONDS = 8.0


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def find_cell(name: str):
    """(workload, config, traffic) of the cell called `name`."""
    bench = benchmark()
    workload = next((w for w in bench["workloads"] if w["name"] == name), None)
    if workload is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    config = load_json(HERE / "configs" / f"{workload['config']}.json")
    traffic = load_json(HERE / "traffic" / f"{workload['traffic']}.json")
    return workload, config, traffic


def load_entry(name: str):
    return importlib.import_module(f"chipbench.entries.{name}")


def load_reader(metric: str):
    """The `read(ctx)` function of `metrics/<metric>.py`."""
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_for(cell: str, kind: str) -> list[dict]:
    """The metrics of `kind` ("end_to_end" or "per_layer") this cell reports:
    those that list it, and those without a list whose end-to-end metric it
    reports."""
    bench = benchmark()
    e2e = [m for m in bench["end_to_end"] if "workloads" not in m or cell in m["workloads"]]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [
        m for m in bench["per_layer"]
        if (cell in m["workloads"] if "workloads" in m else m["moves"] in names)
    ]


def limits_for(cell: str) -> dict:
    return load_json(HERE / "limits" / f"{cell}.json")["limits"]


def setup_jax(require_chip: bool, chips: int):
    """Import JAX, check for the chips the cell needs, and point the
    persistent compilation cache at the checkout's fixed directory."""
    import jax

    devices = jax.devices()
    if require_chip and devices[0].platform == "cpu":
        raise SystemExit("chipbench: JAX found no accelerator")
    if require_chip and len(devices) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips, JAX found {len(devices)}")
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax, devices


class CompileCounter:
    """Counts the programs JAX traces and compiles, through its monitoring
    events, while open; the window should see none."""

    def __enter__(self):
        from jax import monitoring

        self.traces = 0
        self.compiles = 0
        monitoring.register_event_duration_secs_listener(self._event)
        return self

    def __exit__(self, *exc):
        from jax import monitoring

        monitoring.unregister_event_duration_listener(self._event)

    def _event(self, name, secs, **kw):
        if name.endswith("backend_compile_duration"):
            self.compiles += 1
        elif name.endswith("jaxpr_trace_duration"):
            self.traces += 1


class RunContext:
    """What a metric reader sees: the set-up time, the window's calls, the
    cell, the device, and with `--trace 1` the reduced trace."""

    def __init__(self, cell, setup_s, records, window_s, device_kind, trace=None):
        self.cell = cell
        self.setup_s = setup_s
        self.records = records
        self.window_s = window_s
        self.device_kind = device_kind
        self.trace = trace

    def durations_s(self) -> np.ndarray:
        return np.asarray([r["t1"] - r["t0"] for r in self.records])

    def peaks(self) -> dict:
        table = load_json(HERE / "peaks.json")["kinds"]
        if self.device_kind not in table:
            raise ValueError(f"no published peaks for device kind {self.device_kind!r}")
        return table[self.device_kind]


def log(*parts):
    print(*parts, file=sys.stderr, flush=True)


class HostReadings:
    """What the process did during a call, to tell a call that waited from
    one in which the host worked: CPU seconds of all its threads, and
    seconds in Python's garbage collector."""

    def __enter__(self):
        self.gc_s, self._gc_t0 = 0.0, None
        gc.callbacks.append(self._gc)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._gc)

    def _gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self.gc_s += time.perf_counter() - self._gc_t0
            self._gc_t0 = None

    def read(self) -> dict:
        ru = resource.getrusage(resource.RUSAGE_SELF)
        return dict(cpu_s=ru.ru_utime + ru.ru_stime, gc_s=self.gc_s)


def window(cell, seconds):
    """Calls back to back until `seconds` have passed: each call's record
    (with what the host did in it, under `host`) and answer, and how many
    calls raised."""
    import jax

    records, answers, failed = [], {}, 0
    with HostReadings() as host:
        t_window = time.perf_counter()
        i = 1
        while True:
            tp = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.prepare"):
                cell.prepare(i)
            h0 = host.read()
            t0 = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation("bench.call"):
                    answers[i] = cell.call(i)
            except Exception as exc:  # a call that raises counts as failed
                failed += 1
                log(f"[window] call {i} raised {type(exc).__name__}: {exc}")
            t1 = time.perf_counter()
            h1 = host.read()
            records.append(dict(i=i, tp=tp, t0=t0, t1=t1, jobs=cell.jobs_per_call,
                                host={k: h1[k] - h0[k] for k in h0}))
            i += 1
            if t1 - t_window >= seconds:
                return records, records[-1]["t1"] - t_window, answers, failed


def log_slowest(name, records, k=3):
    """The k slowest calls of the window beside its median call, with what
    the host did in each."""
    by_wall = sorted(records, key=lambda r: r["t1"] - r["t0"])
    picks = [("median", by_wall[len(by_wall) // 2])] + [("slow", r) for r in by_wall[-k:][::-1]]
    for tag, r in picks:
        h = r["host"]
        log(f"[host] {name}: {tag} call {r['i']} wall_s={r['t1'] - r['t0']:.6f} "
            f"cpu_s={h['cpu_s']:.6f} gc_s={h['gc_s']:.6f}")


def program_bytes(cell) -> int:
    """Bytes the cell's device program holds while it runs: arguments,
    results and temporaries, by the compiled program's memory analysis (0
    where the backend gives none)."""
    ma = cell.lowered().compile().memory_analysis()
    if ma is None:
        return 0
    parts = dict(arguments=ma.argument_size_in_bytes, results=ma.output_size_in_bytes,
                 aliased=ma.alias_size_in_bytes, temporaries=ma.temp_size_in_bytes)
    log("[memory] program " + " ".join(f"{k}={v}" for k, v in parts.items()))
    return int(parts["arguments"] + parts["results"] - parts["aliased"] + parts["temporaries"])


def compare(cell, answers, k, seed, limits) -> dict:
    """The largest reading of each compared number over k answers drawn
    from the seed, beside its limit."""
    done = sorted(answers)
    rng = np.random.default_rng([abs(int(seed)), 0x5EED])
    sample = sorted(rng.choice(done, size=min(k, len(done)), replace=False).tolist())
    t0 = time.perf_counter()
    numbers = {}
    for j in sample:
        for key, value in cell.compare(j, answers[j]).items():
            numbers[key] = max(numbers.get(key, 0.0), value)
    log(f"[check] calls {sample} compared in {time.perf_counter() - t0:.3f} s")
    return {key: {"value": numbers.get(key, math.inf), "limit": limit}
            for key, limit in limits.items()}


def run_cell(workload, config, traffic, seed, seconds, trace, *, require_chip=True,
             t_start=None, keep_trace=None):
    """One run of one cell; returns the result line's object."""
    t_start = time.perf_counter() if t_start is None else t_start
    name = workload["name"]
    jax, devices = setup_jax(require_chip, workload["chips"])
    with CompileCounter() as counter:
        cell = load_entry(traffic["entry"]).build(config, traffic, seed)
        # set-up: one call of the cell's own shapes compiles (or loads)
        # every program the window runs
        cell.prepare(0)
        cell.call(0)
        compiles, traces = counter.compiles, counter.traces
        setup_s = time.perf_counter() - t_start
        log(f"[setup] {name}: setup_s={setup_s:.3f} programs compiled or loaded={compiles}")
        tracer = None
        if trace:
            from chipbench import tracereduce

            tracer = tracereduce.Tracer(ROOT / ".chipbench_trace" / name)
            tracer.start()
            seconds = min(seconds, TRACE_SECONDS)
        records, window_s, answers, failed = window(cell, seconds)
        reading = tracer.stop(keep_trace) if tracer is not None else None
        calls = np.asarray([r["t1"] - r["t0"] for r in records])
        prep = np.asarray([r["t0"] - r["tp"] for r in records])
        log(f"[window] {name}: {len(records)} calls in {window_s:.3f} s; call s min "
            f"{calls.min():.6f} median {np.median(calls):.6f} max {calls.max():.6f}; "
            f"prepare s median {np.median(prep):.6f}; compiles in window="
            f"{counter.compiles - compiles} traces in window={counter.traces - traces}")
        log_slowest(name, records)

    used = devices[: workload["chips"]] if require_chip else devices[:1]
    allocator = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in used)
    # the allocator's peak leaves out a program's temporaries on some
    # backends; the program holds at least its own bytes while it runs
    program = program_bytes(cell)
    peak = max(allocator, program)
    log(f"[memory] {name}: allocator peak_bytes_in_use={allocator} program={program} "
        f"memory_peak_bytes={peak}")
    kind = used[0].device_kind
    ctx = RunContext(cell, setup_s, records, window_s, kind, reading)
    cell.release()
    compared = compare(cell, answers, traffic["check_calls"], seed, limits_for(name))
    correct = failed == 0 and all(c["value"] <= c["limit"] for c in compared.values())

    metrics = {}
    for m in metrics_for(name, "per_layer" if trace else "end_to_end"):
        value = load_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = dict(platform=used[0].platform, kind=kind, count=len(devices),
                  memory_peak_bytes=peak)
    out = dict(correct=correct, attempted=len(records), failed=failed, metrics=metrics,
               device=device)
    if reading is not None:
        device["busy_s"] = reading.busy_s
        device["window_s"] = reading.window_s
        out["breakdown"] = reading.breakdown()
        for line in reading.summary_lines():
            log(f"[trace] {line}")
    for key, c in compared.items():
        log(f"compared {key} {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}")
    out["compared"] = compared
    return out
