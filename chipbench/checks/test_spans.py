"""The readers of the program's grid spans against hand counts.

    python -m pytest chipbench/checks/test_spans.py
"""

from __future__ import annotations

import pytest

from chipbench import harness, spans
from chipbench import tracereduce as T
from chipbench.checks.test_tracereduce import DRAWS, QUEUE, hand_record

US = 1000


def grid_record():
    # two calls, [0, 20] and [22, 40] µs, each split into the four grid
    # spans with host code around them: call 1 [0, 1] and [18, 20], call 2
    # [22, 23] and [39, 40].  Ops: an upload inside call 1's lowering, the
    # two programs, each from inside its dispatch into its fetch, and an op
    # in call 1's closing host code; a runtime span inside the second fetch
    # must not be counted again
    return {
        "devices": {"/device:TPU:0": [
            ["copy.1", 1.5 * US, 2 * US, ""],
            ["fusion.2", 4 * US, 10 * US, DRAWS],
            ["copy.3", 19 * US, 19.5 * US, ""],
            ["while.4", 25 * US, 33 * US, QUEUE],
        ]},
        "host": [
            ["bench.call", 0 * US, 20 * US, "main"],
            ["grid.lower", 1 * US, 3 * US, "main"],
            ["grid.dispatch", 3 * US, 5 * US, "main"],
            ["grid.fetch", 5 * US, 12 * US, "main"],
            ["grid.tail", 12 * US, 18 * US, "main"],
            ["bench.prepare", 20 * US, 22 * US, "main"],
            ["bench.call", 22 * US, 40 * US, "main"],
            ["grid.lower", 23 * US, 24 * US, "main"],
            ["grid.dispatch", 24 * US, 26 * US, "main"],
            ["grid.fetch", 26 * US, 35 * US, "main"],
            ["TransferFromDevice", 33 * US, 34 * US, "main"],
            ["grid.tail", 35 * US, 39 * US, "main"],
        ],
    }


# idle µs inside each span, call 1 + call 2, over 2 calls, in ms:
# lower (2 - 0.5) + 1; dispatch (2 - 1) + (2 - 1); fetch (7 - 5) + (9 - 7);
# tail 6 + 4
HAND = {"lower_ms.eval": 1.25e-3, "dispatch_ms.eval": 1.0e-3,
        "fetch_ms.eval": 2.0e-3, "host_tail_ms.eval": 5.0e-3}
# idle µs of the host code between the spans: (3 - 0.5) + 2, over 2 calls
GLUE_MS = 2.25e-3


class Ctx:
    def __init__(self, record):
        self.trace = T.Reading(record)


@pytest.mark.parametrize("metric", sorted(HAND))
def test_reader_against_hand_count(metric):
    assert harness.load_reader(metric)(Ctx(grid_record())) == pytest.approx(HAND[metric])


def test_readers_and_glue_split_host_time():
    r = T.Reading(grid_record())
    # host per call: call 1 20 - 7 busy, call 2 18 - 8
    assert r.host_ms_per_call() == pytest.approx(11.5e-3)
    total = sum(harness.load_reader(m)(Ctx(grid_record())) for m in HAND)
    assert total + GLUE_MS == pytest.approx(r.host_ms_per_call())


def test_device_time_is_averaged_over_chips():
    # a second chip idle throughout: each span's idle time is its length
    # less half the first chip's overlap
    rec = grid_record()
    rec["devices"]["/device:TPU:1"] = []
    r = T.Reading(rec)
    assert spans.idle_ms_per_call(r, "grid.fetch") == pytest.approx((4.5 + 5.5) / 2 * 1e-3)


@pytest.mark.parametrize("metric", sorted(HAND))
def test_reader_gives_none_without_its_span(metric):
    assert harness.load_reader(metric)(Ctx(hand_record())) is None
