"""The trace reduction against hand counts.

    python -m pytest chipbench/checks/test_tracereduce.py

One record is made by hand, with its answers worked out below; the other,
`trace_sample.json.gz`, was recorded on a TPU v5e from a short traced run of
a re-plan cell (`record_trace.py`; the controller's re-plan, three short
searches a call, which gives many calls and gaps in a small record), and
its busy time, idle share, host time and gaps are counted again here on a
1 µs grid.
"""

from __future__ import annotations

from pathlib import Path

import numpy as np
import pytest

from chipbench import tracereduce as T

SAMPLE = Path(__file__).with_name("trace_sample.json.gz")

DRAWS = "repro.fleet.vector.emp_quantile"
QUEUE = "repro.fleet.vector.kw_queue.<locals>.step"
POLICY = "repro.fleet.vector.masked_single_fork"


def hand_record():
    # two calls, [0, 15] and [18, 40] µs; ops (in ns): a draws op of 10 µs
    # holding a nested queue op of 2, a policy op, a draws op that starts
    # before the second call and runs 5 µs into it, holding an op whose
    # metadata names no function, and a copy that names none either
    us = 1000
    return {
        "devices": {"/device:TPU:0": [
            ["fusion.1", 0 * us, 10 * us, DRAWS],
            ["while.2", 2 * us, 4 * us, QUEUE],
            ["fusion.3", 12 * us, 14 * us, POLICY],
            ["fusion.4", 16 * us, 23 * us, DRAWS],
            ["dynamic-update-slice.6", 17 * us, 18 * us, ""],
            ["copy.5", 30 * us, 31 * us, ""],
        ]},
        "host": [
            ["bench.call", 0 * us, 15 * us, "main"],
            ["bench.prepare", 15 * us, 18 * us, "main"],
            ["bench.call", 18 * us, 40 * us, "main"],
            ["PJRT_LoadedExecutable_Execute", 24 * us, 26 * us, "main"],
        ],
    }


def test_hand_record():
    r = T.Reading(hand_record())
    assert r.n_calls == 2
    assert r.window_s == pytest.approx(40e-6)
    # union of ops: [0,10] + [12,14] + [16,23] + [30,31] = 20 µs
    assert r.busy_s == pytest.approx(20e-6)
    assert r.idle_share_pct() == pytest.approx(50.0)
    # self times: fusion.1 10 - 2 nested = 8, fusion.4 7 - 1 = 6 (the op
    # counts whole; its first 2 µs lie before the second call), and the op
    # nested in it 1, which takes fusion.4's function; the copy has none
    assert r.layer_s["draws"] == pytest.approx(15e-6)
    assert r.layer_s["queue"] == pytest.approx(2e-6)
    assert r.layer_s["policy"] == pytest.approx(2e-6)
    assert r.layer_s["other"] == pytest.approx(1e-6)
    assert r.layer_ms_per_call("draws") == pytest.approx(7.5e-3)
    # host per call: call 1 15 - 12 busy = 3; call 2 22 - (5 + 1) = 16
    assert r.host_ms_per_call() == pytest.approx(9.5e-3)
    # gaps [10,12], [14,16], [23,30], [31,40]; at 15 the prepare span is
    # the innermost open; at 26.5 the runtime span has closed
    got = sorted((name, round(s * 1e6, 6)) for name, s in r.gaps())
    assert got == [("bench.call", 2.0), ("bench.call", 7.0), ("bench.call", 9.0),
                   ("bench.prepare", 2.0)]


def test_innermost_span_names_the_gap():
    rec = hand_record()
    rec["host"].append(["TransferFromDevice", 32_000, 38_000, "worker"])
    r = T.Reading(rec)
    assert ("TransferFromDevice", pytest.approx(9e-6)) in [(n, s) for n, s in r.gaps()]


def test_function_of_resolves_nested_names():
    src = Path(T.__file__).resolve()
    line = next(i for i, text in enumerate(src.read_text().splitlines(), 1)
                if "def _union(" in text)
    assert T.function_of(f"{src}:{line + 2}").endswith("tracereduce._union")
    assert T.layer_of("repro.fleet.vector._frontier_jit.<locals>.cellstats.<locals>.f") == "tail"
    assert T.layer_of("repro.fleet.vector.emp_quantile") == "draws"
    assert T.layer_of("") == "other"


def grid_count(record):
    """Busy time, per-call host time and idle time counted on a 1 µs grid."""
    calls = sorted((s, e) for n, s, e, _ in record["host"] if n == "bench.call")
    t0, t1 = calls[0][0], calls[-1][1]
    size = int(np.ceil((t1 - t0) / 1000.0)) + 1
    busy = np.zeros(size, bool)
    (ops,) = record["devices"].values()
    for _, s, e, _ in ops:
        a, b = max(s, t0), min(e, t1)
        if b > a:
            busy[int(round((a - t0) / 1000.0)):int(round((b - t0) / 1000.0))] = True
    host = []
    for s, e in calls:
        a, b = int(round((s - t0) / 1000.0)), int(round((e - t0) / 1000.0))
        host.append((b - a) - busy[a:b].sum())
    return busy.sum() * 1e-6, float(np.mean(host)) * 1e-3, (t1 - t0) * 1e-9


def test_recorded_sample():
    record = T.read_record(SAMPLE)
    r = T.Reading(record)
    busy_s, host_ms, window_s = grid_count(record)
    assert r.window_s == pytest.approx(window_s)
    assert r.busy_s == pytest.approx(busy_s, rel=1e-3)
    assert r.host_ms_per_call() == pytest.approx(host_ms, rel=1e-2, abs=0.01)
    assert 0 < r.idle_share_pct() < 100
    # every op's self time lands in exactly one layer, and the gaps fill
    # what the device left idle
    (ops,) = record["devices"].values()
    inside = [op for op in ops if op[2] > r.t0 and op[1] < r.t1]
    assert sum(r.layer_s.values()) == pytest.approx(
        sum(T._nesting(inside)[0]) * 1e-9)
    assert sum(s for _, s in r.gaps()) == pytest.approx(r.window_s - r.busy_s, rel=1e-6)
    assert r.layer_s.get("draws", 0) > r.layer_s.get("queue", 0) > 0
