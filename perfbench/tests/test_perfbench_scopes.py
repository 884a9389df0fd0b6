"""Device time by layer scope: the innermost-scope rule, the reduction
and the five layer readers on a hand-made trace with known
answers, and what they read where a trace carries no scopes."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

from harness import scopes, trace  # noqa: E402
from harness.common import load_module  # noqa: E402

MS = 1_000_000
READERS = ("grad_eval_ms_per_round", "flat_ms_per_round",
           "delta_sgd_step_ms_per_round", "round_tail_ms_per_round",
           "host_sync_ms_per_round")
PAIR = ("%closed_call.1 = (f32[4]{0}, f32[4]{0}) custom-call(f32[4,8,128]"
        "{2,1,0} %a, f32[4,8,128]{2,1,0} %b), "
        'custom_call_target="tpu_custom_call"')
BODY = "jit(loop_fn)/while/body/closed_call/while/body/closed_call"


def reader(name):
    return load_module(os.path.join(BENCH, "metrics", name + ".py"))


def test_innermost_layer_scope():
    assert scopes.layer_of(BODY + "/client_grad/vmap(jvp())/dot_general") \
        == "client_grad"
    assert scopes.layer_of(BODY + "/round_tail/flat/concatenate") == "flat"
    assert scopes.layer_of("jit(f)/transpose(jvp(delta_sgd))/mul") == \
        "delta_sgd"
    assert scopes.layer_of(BODY + "/dynamic_update_slice") is None
    assert scopes.layer_of("jit(flatten)/flatter/reshape") is None
    assert scopes.layer_of("") is None


def hand_trace():
    """Two devices over a 10 ms window, 2 rounds. Device 0: a loop op
    0-10 ms with no scope holding the gradient 0-4 ms, a flat pass 4-5
    ms, the kernel pair 5-6 ms and its select 5.5-6.5 ms (overlapping),
    a flat pass inside the tail 6.5-7 ms, the tail 7-8 ms, and an
    unscoped copy 8-9 ms; a gradient op that starts before the window.
    Device 1: the gradient 0-2 ms only."""
    d0 = [["%while.1 = while()", -MS, 11 * MS, BODY],
          ["%fusion.1 = fusion()", -MS, 2 * MS,
           BODY + "/client_grad/vmap(jvp())/dot_general"],
          ["%fusion.2 = fusion()", MS, 3 * MS,
           BODY + "/client_grad/vmap(transpose(jvp()))/dot_general"],
          ["%fusion.3 = fusion()", 4 * MS, MS, BODY + "/flat/concatenate"],
          [PAIR, 5 * MS, MS, BODY + "/delta_sgd/pallas_call"],
          ["%select.1 = select()", 5 * MS + MS // 2, MS,
           BODY + "/delta_sgd/jit(_where)/select_n"],
          ["%fusion.4 = fusion()", 6 * MS + MS // 2, MS // 2,
           BODY + "/round_tail/flat/concatenate"],
          ["%fusion.5 = fusion()", 7 * MS, MS, BODY + "/round_tail/add"],
          ["%copy.1 = copy()", 8 * MS, MS, BODY + "/copy"]]
    d1 = [["%fusion.1 = fusion()", 0, 2 * MS,
           BODY + "/client_grad/vmap(jvp())/dot_general"]]
    devices = {k: {"ops": [e[:3] for e in d], "scopes": [e[3] for e in d]}
               for k, d in (("0", d0), ("1", d1))}
    return {"devices": devices,
            "host": [["bench.window", 0, 10 * MS],
                     ["bench.fetch", 9 * MS, MS]],
            "window": [0, 10 * MS]}


def test_scope_time_on_a_hand_made_trace():
    tr = hand_trace()
    # device 0: 0-1 (clipped) + 1-4 = 4 ms; device 1: 2 ms
    assert scopes.scope_time_ns(tr, "client_grad") == \
        pytest.approx((4 + 2) / 2 * MS)
    # 4-5 and, innermost inside the tail, 6.5-7
    assert scopes.scope_time_ns(tr, "flat") == pytest.approx(1.5 / 2 * MS)
    # the pair and its select overlap: 5-6.5 counted once
    assert scopes.scope_time_ns(tr, "delta_sgd") == \
        pytest.approx(1.5 / 2 * MS)
    assert scopes.scope_time_ns(tr, "round_tail") == \
        pytest.approx(1.0 / 2 * MS)
    assert scopes.scope_time_ns(tr, "serve") is None
    # the unscoped rest of the busy time: on device 0 the copy 8-9 ms
    # and the loop op alone 9-10 ms
    busy = trace.busy_s(tr) * 1e9
    layers = sum(scopes.scope_time_ns(tr, s) for s in scopes.LAYERS)
    assert busy - layers == pytest.approx(2.0 / 2 * MS)


def test_readers_on_a_hand_made_trace():
    ctx = {"trace": hand_trace(), "counts": {
        "rounds": 2, "spans": {"stage": (0.004, 2), "fetch": (0.0006, 2)}}}
    got = {n: reader(n).read(ctx) for n in READERS}
    assert got == pytest.approx({
        "grad_eval_ms_per_round": 3.0 / 2,
        "flat_ms_per_round": 0.75 / 2,
        "delta_sgd_step_ms_per_round": 0.75 / 2,
        "round_tail_ms_per_round": 0.5 / 2,
        "host_sync_ms_per_round": 0.3})
    # the step holds the pair the shape-matching reader finds
    ctx["counts"]["pair_shape"] = "f32[4,8,128]"
    assert got["delta_sgd_step_ms_per_round"] >= \
        reader("delta_sgd_ms_per_round").read(ctx)


def test_readers_read_nothing_without_scopes(monkeypatch, tmp_path):
    """The first recorded slice was traced before the program had
    scopes, and its spans carry the old names: every new reader says
    so with None, and none raises."""
    monkeypatch.setattr(scopes, "TRACE_ROOT", str(tmp_path))
    with open(os.path.join(HERE, "data", "lm_trace_slice.json")) as f:
        tr = json.load(f)["trace"]
    ctx = {"trace": tr, "counts": {"rounds": 2, "spans": {
        "stage": (0.1, 2), "block_execute": (0.01, 2),
        "convert": (0.5, 2)}}}
    assert {n: reader(n).read(ctx) for n in READERS} == \
        dict.fromkeys(READERS)


def test_scoped_takes_the_xplane_of_its_own_window(monkeypatch, tmp_path):
    """A reader gets the reduced trace, not the file: ``scoped`` reads
    the newest xplane under the trace root and takes it only where its
    window is the reduced trace's."""
    d = tmp_path / "cell" / "plugins"
    d.mkdir(parents=True)
    (d / "a.xplane.pb").write_bytes(b"")
    read = []
    sc = hand_trace()

    def fake(path):
        read.append(path)
        return sc
    monkeypatch.setattr(scopes, "from_xplane", fake)
    plain = {"devices": {"0": {"ops": sc["devices"]["0"]["ops"]}},
             "host": [], "window": [0, 10 * MS]}
    assert scopes.scoped(plain, root=str(tmp_path)) is sc
    assert scopes.scoped(dict(plain, window=[0, 9 * MS]),
                         root=str(tmp_path)) is None
    assert len(read) == 1                       # read once, then cached
    assert scopes.scoped(sc) is sc              # scopes already there
    assert scopes.scoped(plain, root=str(tmp_path / "none")) is None


def test_from_xplane_keeps_the_window(tmp_path):
    """On the CPU the profiler writes no TPU plane: the scoped read has
    the window of ``trace.from_xplane`` and no devices."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = trace.newest_xplane(str(tmp_path))
    got = scopes.from_xplane(path)
    assert got["window"] == trace.from_xplane(path)["window"]
    assert got["devices"] == {}
    assert scopes.scope_time_ns(got, "flat") is None


SCOPED = os.path.join(HERE, "data", "lm_scoped_slice.json")


def test_reductions_on_a_recorded_scoped_slice():
    """One round of the lm cell traced on the chip with its layer
    scopes. The expected times were worked out by a sweep over the op
    intervals, not by the code under test."""
    with open(SCOPED) as f:
        rec = json.load(f)
    tr, want = rec["trace"], rec["expected"]
    for s in scopes.LAYERS:
        assert scopes.scope_time_ns(tr, s) == \
            pytest.approx(want["scope_ns"][s], rel=1e-9), s
    busy = trace.busy_s(tr) * 1e9
    assert busy == pytest.approx(want["busy_ns"], rel=1e-9)
    # the four layers hold nine tenths of the busy time; the rest is
    # ops the compiler made with no op_name (copies, a select, stacking)
    assert 0.9 * busy <= sum(want["scope_ns"].values()) < busy
    ctx = {"trace": tr, "counts": {"rounds": want["rounds"],
                                   "pair_shape": want["pair_shape"]}}
    got = {n: reader(n).read(ctx) for n in READERS[:4]}
    assert got == pytest.approx({
        "grad_eval_ms_per_round": want["scope_ns"]["client_grad"] / 1e6,
        "flat_ms_per_round": want["scope_ns"]["flat"] / 1e6,
        "delta_sgd_step_ms_per_round": want["scope_ns"]["delta_sgd"] / 1e6,
        "round_tail_ms_per_round": want["scope_ns"]["round_tail"] / 1e6})
    pair = reader("delta_sgd_ms_per_round").read(ctx)
    assert pair == pytest.approx(want["pair_ns"] / 1e6, rel=1e-9)
    assert got["delta_sgd_step_ms_per_round"] >= pair
    # the stage gap before the block, named by the repaired spans
    assert trace.idle_gaps(tr)[0][0] == "bench.stage"
    names = {h[0] for h in tr["host"]}
    assert {"bench.stage", "bench.dispatch", "bench.wait"} <= names
    assert not names & {"bench.block_execute", "bench.convert"}
