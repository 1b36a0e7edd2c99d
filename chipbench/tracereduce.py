"""From a profiler trace of the window to the per-layer numbers.

A traced run records the window under `jax.profiler`.  `load` turns the
trace into a compact record: the device operations of each chip (name,
start, end, and the program function its source location lies in) and the
host spans of the benchmark's own annotations (`bench.call`,
`bench.prepare`) and of JAX's runtime.  A `Reading` reduces that record:

* the window runs from the first `bench.call` span's start to the last
  one's end;
* device busy time is the union of the operations' intervals in the window,
  averaged over the chips; the idle share is 1 - busy / window;
* per call, host time is the call's wall time less the device's busy time
  inside it;
* an operation belongs to the layer that `layers.json` gives the function
  its source location lies in (found by name, so edits inside a function do
  not move it), or, where its metadata names none, to that of the operation
  it is nested in; a layer's device time is the self time of its
  operations, nested operations counted once;
* each idle gap inside the window is put down to the innermost host span
  open at its middle.
"""

from __future__ import annotations

import ast
import gzip
import json
import re
import shutil
from functools import lru_cache
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
CALL = "bench.call"


# ------------------------------------------------------------- recording


class Tracer:
    """Starts and stops the profiler around the window."""

    def __init__(self, directory: Path):
        self.directory = Path(directory)

    def start(self):
        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        self.directory.mkdir(parents=True, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.directory), profiler_options=opts)

    def stop(self, keep=None):
        """Stop, reduce and delete the trace; `keep` is a path to save its
        compact record to."""
        import jax

        jax.profiler.stop_trace()
        path = max(self.directory.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
        record = load(path)
        shutil.rmtree(self.directory, ignore_errors=True)
        if keep is not None:
            save(record, keep)
        return Reading(record)


# A profiler trace is an XSpace protobuf.  Only the few fields read here are
# decoded, by hand, so that the reduction needs nothing but the standard
# library: XSpace.planes (1); XPlane.name (2), .lines (3), .event_metadata
# (4, a map), .stat_metadata (5, a map); XLine.name (2), .timestamp_ns (3),
# .events (4); XEvent.metadata_id (1), .offset_ps (2), .duration_ps (3);
# XEventMetadata.id (1), .name (2), .stats (5); XStatMetadata.id (1),
# .name (2); XStat.metadata_id (1), .str_value (5).


def _varint(b, i):
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        s += 7
        if c < 0x80:
            return r, i


def _fields(b):
    """(field number, value) of a message: ints for varints, bytes else."""
    i, n = 0, len(b)
    while i < n:
        k, i = _varint(b, i)
        f, t = k >> 3, k & 7
        if t == 0:
            v, i = _varint(b, i)
        elif t == 2:
            ln, i = _varint(b, i)
            v = b[i:i + ln]
            i += ln
        elif t == 1:
            v, i = b[i:i + 8], i + 8
        elif t == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {t}")
        yield f, v


def _map_value(entry):
    return next((v for f, v in _fields(entry) if f == 2), b"")


def _plane(b):
    """name, lines, {metadata id: (name, {stat name: str value})}."""
    name, lines, ev_md, stat_names = "", [], [], {}
    for f, v in _fields(b):
        if f == 2:
            name = v.decode()
        elif f == 3:
            lines.append(v)
        elif f == 4:
            ev_md.append(_map_value(v))
        elif f == 5:
            md = dict(_fields(_map_value(v)))
            stat_names[md.get(1, 0)] = md.get(2, b"").decode()
    meta = {}
    for m in ev_md:
        mid, mname, stats = 0, "", {}
        for f, v in _fields(m):
            if f == 1:
                mid = v
            elif f == 2:
                mname = v.decode(errors="replace")
            elif f == 5:
                st = dict(_fields(v))
                if 5 in st:
                    stats[stat_names.get(st.get(1, 0), "")] = st[5].decode(errors="replace")
        meta[mid] = (mname, stats)
    return name, lines, meta


def _line(b, want=None):
    """Name and events [(metadata id, start ns, end ns)] of a line; the
    events only where `want(name)` holds."""
    name, ts, raw = "", 0, []
    for f, v in _fields(b):
        if f == 2:
            name = v.decode()
        elif f == 3:
            ts = v
        elif f == 4:
            raw.append(v)
    if want is not None and not want(name):
        return name, []
    events = []
    for e in raw:
        mid = off = dur = 0
        for f, v in _fields(e):
            if f == 1:
                mid = v
            elif f == 2:
                off = v
            elif f == 3:
                dur = v
        start = ts + off / 1000.0
        events.append((mid, start, start + dur / 1000.0))
    return name, events


def load(path) -> dict:
    """The compact record of an `.xplane.pb` trace: per device, its ops as
    [HLO name, start ns, end ns, function]; and every host span with a
    duration, as [name, start ns, end ns, thread]."""
    data = Path(path).read_bytes()
    devices, host = {}, []
    for f, b in _fields(data):
        if f != 1:
            continue
        name, lines, meta = _plane(b)
        if DEVICE_PLANE.match(name):
            ops = []
            for lb in lines:
                _, events = _line(lb, lambda n: n == OPS_LINE)
                for mid, s, e in events:
                    mname, stats = meta.get(mid, ("", {}))
                    ops.append([mname.split(" = ")[0].lstrip("%"), s, e,
                                function_of(stats.get("source", ""))])
            devices[name] = ops
        elif name.startswith("/host:"):
            for lb in lines:
                lname, events = _line(lb)
                for mid, s, e in events:
                    if e > s:
                        host.append([meta.get(mid, ("", {}))[0], s, e, lname])
    return {"devices": devices, "host": host}


def save(record: dict, path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(record, f)


def read_record(path) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


# ------------------------------------------- source location -> function


@lru_cache(maxsize=None)
def _functions(path: str):
    """(first line, last line, qualified name) of every function in a file."""
    try:
        tree = ast.parse(Path(path).read_text())
    except (OSError, SyntaxError, ValueError):
        return ()
    module = _module_name(path)
    out = []

    def walk(node, prefix):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                name = f"{prefix}.{child.name}"
                out.append((child.lineno, child.end_lineno, name))
                walk(child, f"{name}.<locals>")
            elif isinstance(child, ast.ClassDef):
                walk(child, f"{prefix}.{child.name}")
            elif isinstance(child, ast.Lambda):
                continue
            else:
                walk(child, prefix)

    walk(tree, module)
    return tuple(out)


def _module_name(path: str) -> str:
    parts = Path(path).with_suffix("").parts
    if "src" in parts:
        parts = parts[len(parts) - parts[::-1].index("src"):]
    return ".".join(parts)


def function_of(source: str) -> str:
    """The qualified name of the innermost function holding `file:line`."""
    m = re.match(r"^(.*):(\d+)$", source or "")
    if not m:
        return ""
    path, line = m.group(1), int(m.group(2))
    best = ""
    best_span = None
    for first, last, name in _functions(path):
        if first <= line <= last and (best_span is None or last - first < best_span):
            best, best_span = name, last - first
    return best


@lru_cache(maxsize=None)
def layer_map() -> dict:
    return json.loads((HERE / "layers.json").read_text())["functions"]


def layer_of(function: str) -> str:
    """The layer of a function: the longest prefix of its qualified name in
    `layers.json`, else "other"."""
    table = layer_map()
    name = function
    while name:
        if name in table:
            return table[name]
        name = name.rpartition(".")[0]
    return "other"


# ------------------------------------------------------------- reduction


def _union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap(merged, s, e) -> float:
    return float(sum(max(0, min(e, b) - max(s, a)) for a, b in merged))


def _nesting(ops):
    """Self time of each op (its duration less that of the ops nested in
    it) and the index of the op it is nested in (-1 for none)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    self_t = [op[2] - op[1] for op in ops]
    parent = [-1] * len(ops)
    stack = []
    for i in order:
        s, e = ops[i][1], ops[i][2]
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][2]:
            self_t[stack[-1]] -= e - s
            parent[i] = stack[-1]
        stack.append(i)
    return self_t, parent


def _functions_of(ops, parent):
    """Each op's function; an op whose metadata names none (the body of a
    loop, a slice inside `jnp.take`) takes that of the op it is nested in."""
    out = [op[3] for op in ops]
    for i in sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2])):
        if not out[i] and parent[i] >= 0:
            out[i] = out[parent[i]]
    return out


class Reading:
    def __init__(self, record: dict):
        calls = sorted((s, e) for name, s, e, _ in record["host"] if name == CALL)
        if not calls:
            raise ValueError("the trace holds no bench.call span")
        self.calls = calls
        self.t0, self.t1 = calls[0][0], calls[-1][1]
        self.window_s = (self.t1 - self.t0) * 1e-9
        self.n_calls = len(calls)
        busy, layers, ops_time = [], {}, {}
        self.merged = {}
        for dev, ops in record["devices"].items():
            ops = [op for op in ops if op[2] > self.t0 and op[1] < self.t1]
            merged = _union([(op[1], op[2]) for op in ops])
            self.merged[dev] = merged
            busy.append(_overlap(merged, self.t0, self.t1))
            self_t, parent = _nesting(ops)
            for op, st, fn in zip(ops, self_t, _functions_of(ops, parent)):
                layer = layer_of(fn)
                layers[layer] = layers.get(layer, 0.0) + st
                # op names repeat across programs: name each by its function too
                key = f"{op[0]} {fn.rpartition('.')[2] or layer}"
                ops_time[key] = ops_time.get(key, 0.0) + (op[2] - op[1])
        n_dev = max(len(busy), 1)
        self.busy_s = sum(busy) * 1e-9 / n_dev
        self.layer_s = {k: v * 1e-9 / n_dev for k, v in layers.items()}
        self.ops_s = {k: v * 1e-9 / n_dev for k, v in ops_time.items()}
        self.host = record["host"]

    # per-layer numbers
    def idle_share_pct(self):
        if self.window_s <= 0 or self.busy_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def busy_s_per_call(self):
        return self.busy_s / self.n_calls if self.busy_s > 0 else None

    def host_ms_per_call(self):
        if not self.merged:
            return None
        per = []
        for s, e in self.calls:
            dev = np.mean([_overlap(m, s, e) for m in self.merged.values()])
            per.append((e - s) - dev)
        return float(np.mean(per)) * 1e-6

    def layer_ms_per_call(self, layer):
        t = self.layer_s.get(layer)
        return t * 1e3 / self.n_calls if t else None

    # what the result line and the logs carry
    def gaps(self):
        """Idle gaps of the first device in the window, longest first, each
        named by the innermost host span open at its middle."""
        if not self.merged:
            return []
        merged = next(iter(self.merged.values()))
        edges = [self.t0] + [x for iv in merged for x in iv] + [self.t1]
        gaps = []
        for a, b in zip(edges[0::2], edges[1::2]):
            a, b = max(a, self.t0), min(b, self.t1)
            if b > a:
                gaps.append(((a + b) / 2, (b - a) * 1e-9))
        gaps.sort()
        spans = sorted(self.host, key=lambda h: h[1])
        active, j, out = [], 0, []
        for mid, length in gaps:
            while j < len(spans) and spans[j][1] <= mid:
                active.append(spans[j])
                j += 1
            active = [h for h in active if h[2] >= mid]
            name = min(active, key=lambda h: h[2] - h[1])[0] if active else "none"
            out.append((name, length))
        return sorted(out, key=lambda g: -g[1])

    def breakdown(self):
        ops = sorted(self.ops_s.items(), key=lambda kv: -kv[1])[:10]
        return {
            "device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in self.gaps()[:10]],
        }

    def summary_lines(self):
        yield (f"window_s={self.window_s:.6f} busy_s={self.busy_s:.6f} calls={self.n_calls} "
               f"idle_share_pct={self.idle_share_pct()}")
        for k, v in sorted(self.layer_s.items(), key=lambda kv: -kv[1]):
            yield f"layer {k}: {v * 1e3 / self.n_calls:.3f} ms per call"
        for k, v in sorted(self.ops_s.items(), key=lambda kv: -kv[1])[:15]:
            yield f"op {k}: {v * 1e3 / self.n_calls:.3f} ms per call"
