"""Trace reduction: interval arithmetic, the reductions on a hand-made
trace with known answers, reading an xplane the profiler wrote, and the
reductions on a small trace recorded on the chip."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from harness import trace  # noqa: E402

MS = 1_000_000


def test_union_and_subtract():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 7), (9, 12)]) == \
        [(0, 2), (3, 5), (7, 9)]
    assert trace.subtract([(0, 4)], [(0, 4)]) == []
    assert trace.total([(0, 3), (5, 8)]) == 6


NORMS = ("%closed_call.1 = (f32[4]{0}, f32[4]{0}) custom-call(f32[4,8,128]"
         "{2,1,0} %a, f32[4,8,128]{2,1,0} %b), "
         'custom_call_target="tpu_custom_call"')
APPLY = ("%closed_call.2 = f32[4,8,128]{2,1,0} custom-call(f32[4,1,1]{2,1,0}"
         " %e, f32[4,8,128]{2,1,0} %p, f32[4,8,128]{2,1,0} %g), "
         'custom_call_target="tpu_custom_call"')
ALLRED = "%all-reduce.1 = f32[64]{0} all-reduce(f32[64]{0} %fusion.2)"
FUSION2 = "%fusion.2 = f32[64]{0} fusion(f32[64]{0} %x), kind=kLoop"


def hand_trace():
    """Two devices over a 10 ms window. Device 0: a loop op over 0-6 ms
    holding a matmul fusion 0-4 ms and the kernel pair 4-6 ms, then an
    all-reduce 6-9 ms of which 6-7 ms overlaps a fusion. Device 1: a
    matmul 0-2 ms only. Host: a step span, then a stage span 9-10 ms."""
    d0 = {"ops": [["%while.1 = (f32[4]) while(f32[4] %t), body=%b", 0,
                   6 * MS],
                  ["%fusion.1 = f32[4,4]{1,0} fusion(f32[4,4] %w)", 0,
                   4 * MS],
                  [NORMS, 4 * MS, 1 * MS],
                  [APPLY, 5 * MS, 1 * MS],
                  [ALLRED, 6 * MS, 3 * MS],
                  [FUSION2, 6 * MS, 1 * MS]]}
    d1 = {"ops": [["%fusion.1 = f32[4,4]{1,0} fusion(f32[4,4] %w)", 0,
                   2 * MS]]}
    return {"devices": {"0": d0, "1": d1},
            "host": [["bench.window", 0, 10 * MS],
                     ["bench.step", 0, 9 * MS + 500_000],
                     ["bench.stage", 9 * MS, MS]],
            "window": [0, 10 * MS]}


def test_op_names():
    assert trace.op_kind(NORMS) == "custom-call"
    assert trace.short_name(NORMS) == \
        "closed_call.1 custom-call tpu_custom_call"
    assert trace.op_kind(ALLRED) == "all-reduce"
    assert trace.op_kind("%all-reduce-start.3 = (f32[8]) "
                         "all-reduce-start(f32[8] %x)") == "all-reduce-start"


def test_self_times_take_out_nested_ops():
    rows = trace.self_times(hand_trace()["devices"]["0"]["ops"])
    own = {trace.short_name(r[0]): (r[2], r[3]) for r in rows}
    assert own["while.1 while"] == (0, False)
    assert own["fusion.1 fusion"] == (4 * MS, True)
    assert own["all-reduce.1 all-reduce"] == (2 * MS, False)


def test_reductions_on_a_hand_made_trace():
    tr = hand_trace()
    # device 0 busy 9 of 10 ms, device 1 busy 2 of 10
    assert trace.idle_share(tr) == pytest.approx((0.1 + 0.8) / 2)
    assert trace.busy_s(tr) == pytest.approx((9 + 2) / 2 / 1e3)
    ns, n = trace.op_time_ns(tr, lambda s: "tpu_custom_call" in s
                             and "f32[4,8,128]" in s)
    assert ns == pytest.approx(2 * MS / 2) and n == 1
    gaps = trace.idle_gaps(tr)
    assert gaps == [["bench.stage", pytest.approx(1e-3)]]
    top = trace.top_ops(tr)
    assert top[0] == ["fusion.1 fusion", pytest.approx(3e-3)]
    assert ["while.1 while", 0.0] not in top[:3]


def test_window_clips_events():
    tr = hand_trace()
    tr["window"] = [2 * MS, 5 * MS]
    assert trace.idle_share(tr) == pytest.approx((0.0 + 1.0) / 2)


def test_from_xplane_reads_host_spans(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("bench.stage"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = trace.from_xplane(trace.newest_xplane(str(tmp_path)))
    names = [h[0] for h in tr["host"]]
    assert "bench.stage" in names and trace.WINDOW_SPAN in names
    lo, hi = tr["window"]
    stage = [h for h in tr["host"] if h[0] == "bench.stage"][0]
    assert lo <= stage[1] and stage[1] + stage[2] <= hi
    # no TPU plane on the CPU: nothing to reduce, and the reductions say
    # so instead of reading 0
    assert tr["devices"] == {} and trace.idle_share(tr) is None


RECORDED = os.path.join(HERE, "data", "lm_trace_slice.json")


def test_reductions_on_a_recorded_chip_trace():
    """The expected numbers were worked out by marking each op's
    microseconds in a bitmap, not by the code under test."""
    with open(RECORDED) as f:
        rec = json.load(f)
    tr, want = rec["trace"], rec["expected"]
    assert trace.idle_share(tr) == pytest.approx(want["idle_share"], rel=1e-4)
    shape = want["pair_shape"]
    ns, n = trace.op_time_ns(tr, lambda s: "tpu_custom_call" in s
                             and shape in s)
    assert ns == pytest.approx(want["pair_ns"], rel=1e-3)
    assert n == want["pair_events"]
    gap = trace.breakdown(tr)["idle_gaps"][0]
    assert gap[0] == want["longest_gap_span"]
    assert gap[1] == pytest.approx(want["longest_gap_s"], abs=2e-6)
    assert sum(v for _, v in trace.top_ops(tr, n=10 ** 6)) == \
        pytest.approx((1 - want["idle_share"]) * trace.window_ns(tr) / 1e9,
                      rel=1e-3)
