"""The reader of ``stage_ahead_share`` on made-up span totals: the
share of ``stage`` spans that also opened ``stage_ahead``, by count and
not by time, and nothing where the driver has no such span."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness.common import load_module  # noqa: E402


def read(spans):
    mod = load_module(os.path.join(BENCH, "metrics",
                                   "stage_ahead_share.py"))
    return mod.read({"counts": {"rounds": 40, "spans": spans}})


@pytest.mark.parametrize("spans,share", [
    # 20 blocks: the first stages up front, the other 19 one block ahead
    ({"stage": (0.84, 20), "stage_ahead": (0.80, 19),
      "dispatch": (0.03, 20), "wait": (4.2, 20), "fetch": (0.02, 20)},
     95.0),
    ({"stage": (0.09, 2), "stage_ahead": (0.01, 1)}, 50.0),
])
def test_stage_ahead_share_counts_spans(spans, share):
    assert read(spans) == pytest.approx(share)


@pytest.mark.parametrize("spans", [
    {"stage": (0.84, 20), "dispatch": (0.03, 20), "wait": (4.9, 20),
     "fetch": (0.02, 20)},                  # a driver that stages in turn
    {},
])
def test_stage_ahead_share_reads_nothing_without_the_span(spans):
    assert read(spans) is None
