"""Wall-time / memory / HLO-byte profiling around jitted functions.

`kernel_profile` is the obs-side wrapper for the fused engines and the
Pallas `kw_queue` kernel: lower + compile once (timed), pull bytes-by-op
from the optimized HLO (`profile_hlo`), ask the compiled executable for
its memory footprint (`memory_analysis()` — temp/argument/output bytes),
then time steady-state execution with `block_until_ready` over a few
repeats.

Results land in three places at once: returned as a plain dict, recorded
as spans/counters on a trace recorder (profiler pid), and gauged into a
metrics registry — so the bench lane, the Perfetto timeline, and the live
metrics view all see the same numbers.
"""

from __future__ import annotations

import re
import time
from collections import defaultdict
from typing import Optional

import jax

from .registry import MetricsRegistry
from .trace import PID_PROFILER, NULL_RECORDER, Recorder, NullRecorder

__all__ = ["kernel_profile", "jit_cache_size", "profile_hlo", "shape_bytes", "RetraceWatch"]

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_OP_RE = re.compile(r"=\s+([a-z0-9]+)\[([0-9,]*)\][^ ]*\s+([a-z0-9_-]+)")


def shape_bytes(dtype: str, dims: str) -> int:
    """Bytes of one HLO array shape (`f32`, `"16,1024"`); 0 for a dtype
    outside the table."""
    nbytes = _DTYPE_BYTES.get(dtype)
    if nbytes is None:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * nbytes


def profile_hlo(hlo_text: str, scan_factor: float = 1.0) -> dict:
    """bytes by op kind.  Ops inside `while` bodies get scan_factor weight
    (= total scanned layers; cost analysis counts bodies once)."""
    agg = defaultdict(float)
    in_body = 0
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if re.match(r"%?[\w.-]*body[\w.-]*\s*\(", stripped) or "_body" in stripped.split("(")[0]:
            if stripped.endswith("{"):
                in_body = 1
        if stripped == "}":
            in_body = 0
        m = _OP_RE.search(line)
        if not m:
            continue
        dtype, dims, op = m.groups()
        weight = scan_factor if in_body else 1.0
        agg[op] += shape_bytes(dtype, dims) * weight
    return dict(agg)


def jit_cache_size(fn) -> Optional[int]:
    """Number of compiled entries in a `jax.jit` function's trace cache,
    or None if the wrapped callable does not expose one.

    A growing cache across calls means the call *re-traced* (new static
    arguments or new input shapes) — the observable behind the fused
    engines' "padded re-plans never recompile" contract."""
    try:
        return int(fn._cache_size())
    except Exception:
        return None


class RetraceWatch:
    """Context manager flagging re-traces of one jitted fn.

    Usage::

        with RetraceWatch(_frontier_jit) as w:
            dispatch(...)
        if w.retraced: rec.count("obs.retrace", w.delta)

    `delta` is 0 (cache hit — the contract held), > 0 (that many fresh
    compilations), or None when the backend exposes no cache counter (the
    contract is then unobservable, not violated)."""

    def __init__(self, fn):
        self.fn = fn
        self.delta: Optional[int] = None

    def __enter__(self) -> "RetraceWatch":
        self._before = jit_cache_size(self.fn)
        return self

    def __exit__(self, *exc) -> None:
        after = jit_cache_size(self.fn)
        if self._before is not None and after is not None:
            self.delta = after - self._before

    @property
    def retraced(self) -> bool:
        return bool(self.delta)


def _memory_analysis(compiled) -> dict:
    """Executable memory footprint (`memory_analysis()`, in bytes)."""
    ma = compiled.memory_analysis()
    return {
        "temp_bytes": int(ma.temp_size_in_bytes),
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "generated_code_bytes": int(ma.generated_code_size_in_bytes),
    }


def kernel_profile(
    fn,
    *args,
    name: str = "kernel",
    static_argnames=None,
    repeats: int = 3,
    recorder: Recorder | NullRecorder = NULL_RECORDER,
    registry: Optional[MetricsRegistry] = None,
    scan_factor: float = 1.0,
    **kwargs,
) -> dict:
    """Compile-and-time `fn(*args, **kwargs)`; returns a profile dict with
    compile_s, best/mean wall_s, bytes-by-op (top HLO movers), and the
    executable's memory footprint."""
    jitted = jax.jit(fn, static_argnames=static_argnames)

    t0 = time.perf_counter()
    compiled = jitted.lower(*args, **kwargs).compile()
    compile_s = time.perf_counter() - t0

    byte_agg = profile_hlo(compiled.as_text(), scan_factor=scan_factor)
    mem = _memory_analysis(compiled)

    times = []
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        out = compiled(*args, **kwargs)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)

    prof = {
        "name": name,
        "compile_s": compile_s,
        "wall_s": min(times),
        "wall_mean_s": sum(times) / len(times),
        "repeats": len(times),
        "hlo_bytes_total": sum(byte_agg.values()),
        "hlo_bytes_by_op": dict(
            sorted(byte_agg.items(), key=lambda kv: -kv[1])[:10]
        ),
        **mem,
    }

    if recorder.enabled:
        wall0 = compile_s  # lay exec spans after the compile span
        recorder.span(f"{name}:compile", "profile", 0.0, compile_s,
                      pid=PID_PROFILER,
                      args={"hlo_bytes_total": prof["hlo_bytes_total"], **mem})
        for i, t in enumerate(times):
            recorder.span(f"{name}:exec", "profile", wall0, t,
                          pid=PID_PROFILER, tid=0, args={"repeat": i})
            wall0 += t
        recorder.count(f"profile.{name}.runs", len(times))
    if registry is not None:
        registry.gauge("kernel_wall_s", {"kernel": name}).set(prof["wall_s"])
        registry.gauge("kernel_compile_s", {"kernel": name}).set(compile_s)
        registry.gauge("kernel_temp_bytes", {"kernel": name}).set(mem["temp_bytes"])
    return prof
