"""Telemetry plane (repro.telemetry + kernels/telemetry): the
non-perturbing contract — `--telemetry` must change NO trained bit on
the host round, the fused loop, or the 8-device block-sharded engine —
plus kernel-vs-reference parity, launch-counter namespacing (the Δ-SGD
2-launch/step budget is counted separately from telemetry launches),
the zero-host-transfer guarantee inside a fused block, the typed
schema registry, the JSONL event log, and the report-layer guards."""
import contextlib
import json
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (flatten_fl_state, get_client_opt, get_server_opt,
                        init_fl_state, make_fl_loop, make_fl_round,
                        make_loss, unflatten_fl_state)
from repro.launch.mesh import make_mesh
from repro.telemetry import (EventLog, SpanTimer, TelemetrySpec,
                             config_hash, kernel_launch_snapshot,
                             load_events, reset_kernel_launches,
                             resolve_telemetry, round_telemetry, schema)

needs8 = pytest.mark.skipif(jax.device_count() < 8,
                            reason="needs >= 8 devices "
                                   "(XLA_FLAGS=--xla_force_host_platform"
                                   "_device_count=8)")

R, C, K, D, E = 4, 8, 3, 96, 18


def _problem(rng):
    def quad(params, batch):
        x32 = params["x"].astype(jnp.float32)
        e32 = params["e"].astype(jnp.float32)
        r = batch["A"] @ x32 - batch["b"] + jnp.sum(e32) * 0.01
        return 0.5 * jnp.mean(r * r) + 0.05 * jnp.mean(e32 * e32), {}

    batches = {"A": jnp.asarray(rng.normal(size=(R, C, K, 4, D)),
                                jnp.float32),
               "b": jnp.asarray(rng.normal(size=(R, C, K, 4)),
                                jnp.float32)}
    params = {"x": jnp.asarray(rng.normal(size=D), jnp.float32),
              "e": jnp.asarray(rng.normal(size=E), jnp.bfloat16)}
    return make_loss(quad), params, batches


def _opts():
    return get_client_opt("delta_sgd"), get_server_opt("fedavg")


def _assert_trees_equal(a, b):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(la, np.float32),
                                      np.asarray(lb, np.float32))


# ------------------------------------------------------------ kernels
def test_lane_histogram_kernel_matches_ref(rng):
    """Pallas histogram == jnp reference EXACTLY (counts are small
    integers in f32), including underflow/overflow bins and NaN lanes
    (NaN counts in no bin)."""
    from repro.kernels.telemetry import lane_histogram, lane_histogram_ref
    edges = jnp.asarray(TelemetrySpec(eta_bins=16).eta_edges())
    x = np.asarray(10.0 ** rng.uniform(-6.0, 3.0, size=257), np.float32)
    x[:3] = [0.0, np.nan, np.inf]
    x = jnp.asarray(x)
    h = lane_histogram(x, edges)
    ref = lane_histogram_ref(x, edges)
    assert h.shape == (16,)
    np.testing.assert_array_equal(np.asarray(h), np.asarray(ref))
    # NaN and +inf lanes count in no bin (bins are [lo, hi) half-open,
    # so the overflow bin [e_-2, inf) excludes inf itself); 0.0 lands
    # in the underflow bin
    assert float(jnp.sum(h)) == x.shape[0] - 2


def test_lane_quantiles_kernel_matches_ref(rng):
    from repro.kernels.telemetry import lane_quantiles, lane_quantiles_ref
    x = jnp.asarray(rng.normal(size=77), jnp.float32)
    q = lane_quantiles(x, Q=11)
    ref = lane_quantiles_ref(x, Q=11)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(ref))
    srt = np.sort(np.asarray(x))
    assert float(q[0]) == srt[0] and float(q[-1]) == srt[-1]


def test_launch_counter_namespaces(rng):
    """Telemetry kernels count in their OWN namespace: running them
    does not move the Δ-SGD counter, and the Δ-SGD 2-launch/step
    invariant is unchanged with telemetry enabled."""
    from repro.kernels.delta_sgd import delta_sgd as dk
    from repro.kernels.telemetry import lane_histogram, lane_quantiles
    reset_kernel_launches()
    edges = jnp.asarray(TelemetrySpec().eta_edges())
    x = jnp.asarray(rng.normal(size=64), jnp.float32)
    lane_histogram(jnp.abs(x), edges)
    lane_quantiles(x)
    snap = kernel_launch_snapshot()
    assert snap.get("telemetry/lane_histogram") == 1
    assert snap.get("telemetry/lane_quantiles") == 1
    assert not any(k.startswith("delta_sgd/") for k in snap)

    # a telemetry-on pallas flat round still traces the Δ-SGD fused
    # pair exactly once (the local-step scan body: 2 trace-time
    # launches, an executed schedule of 2·K) — telemetry adds only its
    # own namespace
    loss, params, batches = _problem(rng)
    copt, sopt = _opts()
    rnd = make_fl_round(loss, copt, sopt, num_rounds=10, flat="pallas",
                        telemetry=True)
    st = init_fl_state(params, sopt)
    reset_kernel_launches()
    jax.jit(rnd).lower(st, jax.tree.map(lambda x: x[0], batches))
    assert dk.launch_count() == 2
    snap = kernel_launch_snapshot()
    assert snap.get("telemetry/lane_histogram", 0) >= 1


# ------------------------------------------- non-perturbing trajectory
@pytest.mark.parametrize("backend", ["xla", "pallas", None])
def test_host_round_bit_exact_on_off(backend, rng):
    """R host rounds with telemetry on == off, bit for bit (flat xla,
    flat pallas, and the vmap tree engine), and the on-run's metrics
    are a strict superset."""
    loss, params, batches = _problem(rng)
    copt, sopt = _opts()
    states, mets = [], []
    for tele in (False, True):
        rnd = jax.jit(make_fl_round(loss, copt, sopt, num_rounds=10,
                                    flat=backend or False,
                                    telemetry=tele))
        st = init_fl_state(params, sopt)
        for r in range(R):
            st, m, _ = rnd(st, jax.tree.map(lambda x, r=r: x[r], batches))
        states.append(st)
        mets.append(m)
    _assert_trees_equal(states[0].params, states[1].params)
    for k in mets[0]:
        np.testing.assert_array_equal(np.asarray(mets[0][k]),
                                      np.asarray(mets[1][k]),
                                      err_msg=f"metric {k}")
    extra = set(mets[1]) - set(mets[0])
    assert "eta_hist" in extra and "loss_deciles" in extra
    B = TelemetrySpec().eta_bins
    assert mets[1]["eta_hist"].shape == (B,)
    # every finite η lane lands in a bin on the flat engines
    if backend is not None:
        assert float(jnp.sum(mets[1]["eta_hist"])) == C


def test_fused_loop_bit_exact_on_off(rng):
    """One R-round fused block with telemetry on == off bit-exact;
    distributions gain the leading R axis from the scan."""
    loss, params, batches = _problem(rng)
    copt, sopt = _opts()
    outs = []
    for tele in (False, True):
        loop = make_fl_loop(loss, copt, sopt, params_like=params,
                            num_rounds=10, rounds_per_call=R, flat="xla",
                            telemetry=tele)
        fst = flatten_fl_state(init_fl_state(params, sopt), loop.layout)
        fst, mets = jax.jit(loop, donate_argnums=0)(fst, batches)
        outs.append((unflatten_fl_state(fst, loop.layout), mets))
    _assert_trees_equal(outs[0][0].params, outs[1][0].params)
    for k in outs[0][1]:
        np.testing.assert_array_equal(np.asarray(outs[0][1][k]),
                                      np.asarray(outs[1][1][k]),
                                      err_msg=f"metric {k}")
    B = TelemetrySpec().eta_bins
    assert outs[1][1]["eta_hist"].shape == (R, B)
    assert outs[1][1]["loss_deciles"].shape == (R, 11)


@needs8
@pytest.mark.slow
def test_block_sharded_bit_exact_and_hist_parity(rng):
    """8-device block engine: telemetry on == off bit-exact, AND the
    psum-assembled η histogram equals the replicated engine's
    bit-for-bit (counts are exact integers in f32, so the widened
    (N+5+B,) packed psum reproduces them exactly)."""
    from repro.sharding.spec import FederationSpec
    loss, params, batches = _problem(rng)
    copt, sopt = _opts()
    mesh = make_mesh((4, 2), ("data", "model"))
    fed = FederationSpec(client_axes=("data",), fsdp_axes=(), tp_axes=())

    def run(block, tele):
        kw = dict(params_like=params, num_rounds=10, rounds_per_call=R,
                  flat="xla", telemetry=tele)
        if block:
            kw.update(mesh=mesh, federation=fed, block_sharded=True)
        loop = make_fl_loop(loss, copt, sopt, **kw)
        fst = flatten_fl_state(init_fl_state(params, sopt), loop.layout)
        fst, mets = jax.jit(loop)(fst, batches)
        return fst, mets

    f_off, m_off = run(True, False)
    f_on, m_on = run(True, True)
    assert float(jnp.max(jnp.abs(f_off.P - f_on.P))) == 0.0
    for k in m_off:
        np.testing.assert_array_equal(np.asarray(m_off[k]),
                                      np.asarray(m_on[k]),
                                      err_msg=f"metric {k}")
    _, m_rep = run(False, True)
    np.testing.assert_array_equal(np.asarray(m_on["eta_hist"]),
                                  np.asarray(m_rep["eta_hist"]))
    assert np.all(np.asarray(m_on["eta_hist"]).sum(axis=1) == C)


def test_fused_block_no_host_transfer(rng):
    """No implicit device->host transfer occurs while a telemetry-on
    fused block executes: the whole R-round call runs under
    jax.transfer_guard("disallow") (explicit staging outside it)."""
    loss, params, batches = _problem(rng)
    copt, sopt = _opts()
    loop = make_fl_loop(loss, copt, sopt, params_like=params,
                        num_rounds=10, rounds_per_call=R, flat="xla",
                        telemetry=True)
    jloop = jax.jit(loop)
    fst = flatten_fl_state(init_fl_state(params, sopt), loop.layout)
    batches = jax.tree.map(jnp.asarray, batches)
    jax.block_until_ready(jloop(fst, batches))          # compile outside
    with jax.transfer_guard("disallow"):
        fst2, mets = jloop(fst, batches)
        jax.block_until_ready((fst2.P, mets))
    assert mets["eta_hist"].shape[0] == R


# ------------------------------------------- layer scopes + host spans
LAYERS = ("client_grad", "flat", "delta_sgd", "round_tail")


def _innermost_layer(op_name):
    """The innermost layer scope of an HLO op_name path (a component
    may be wrapped by a JAX transform, ``jvp(flat)``), or None."""
    for comp in reversed(op_name.split("/")):
        name = comp.rsplit("(", 1)[-1].rstrip(")")
        if name in LAYERS:
            return name
    return None


@pytest.mark.parametrize("engine", [
    "fused", pytest.param("block", marks=needs8)])
def test_round_bodies_name_their_layers(engine, rng):
    """The compiled fused loop carries the four layer scopes in its HLO
    op_name metadata, and every dot of the gradient sits under
    ``client_grad``: the device trace can split a round by layer."""
    import re
    loss, params, batches = _problem(rng)
    copt, sopt = _opts()
    kw = dict(params_like=params, num_rounds=10, rounds_per_call=R,
              flat="xla")
    if engine == "block":
        from repro.sharding.spec import FederationSpec
        kw.update(mesh=make_mesh((4, 2), ("data", "model")),
                  federation=FederationSpec(client_axes=("data",),
                                            fsdp_axes=(), tp_axes=()),
                  block_sharded=True)
    loop = make_fl_loop(loss, copt, sopt, **kw)
    fst = flatten_fl_state(init_fl_state(params, sopt), loop.layout)
    hlo = jax.jit(loop).lower(fst, batches).compile().as_text()
    layers = {_innermost_layer(n)
              for n in re.findall(r'op_name="([^"]+)"', hlo)}
    assert set(LAYERS) <= layers
    dots = [ln for ln in hlo.splitlines() if " dot(" in ln]
    assert dots
    for ln in dots:
        name = re.search(r'op_name="([^"]+)"', ln).group(1)
        assert _innermost_layer(name) == "client_grad", ln


def _drive_blocks(rng, rounds, spans):
    """``rounds`` rounds of the tiny problem through the fused block
    driver, R rounds a block."""
    from repro.launch.train import _run_fused
    loss, params, batches = _problem(rng)
    copt, sopt = _opts()
    loop = make_fl_loop(loss, copt, sopt, params_like=params,
                        num_rounds=rounds, rounds_per_call=R, flat="xla")
    args = types.SimpleNamespace(rounds_per_call=R, ckpt_dir=None,
                                 ckpt_every=10 ** 9, profile=0)
    rows = []
    state = _run_fused(args, loop, init_fl_state(params, sopt), rounds,
                       lambda round0, n: (batches, None),
                       lambda t, row: rows.append(row), spans=spans)
    assert len(rows) == rounds and int(state.round) == rounds
    return state


def test_run_fused_spans_once_a_block(rng):
    """The block driver's host spans: stage, dispatch, wait and fetch
    once a block, stage_ahead once a block but the first, pack and
    unpack once a run, nothing else."""
    spans = SpanTimer()
    _drive_blocks(rng, 3 * R, spans)
    s = spans.summary()
    assert set(s) == {"pack", "stage", "stage_ahead", "dispatch", "wait",
                      "fetch", "unpack"}
    for name in ("stage", "dispatch", "wait", "fetch"):
        assert s[name]["n"] == 3, name
    assert s["stage_ahead"]["n"] == 3 - 1
    assert s["pack"]["n"] == s["unpack"]["n"] == 1


def test_span_timer_writes_host_events_to_a_trace(rng, tmp_path):
    """Under a jax.profiler trace every SpanTimer span is a host event
    named ``repro.<span>``, on the trace's own clock."""
    import glob
    import os
    _drive_blocks(rng, R, SpanTimer())          # compile outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        _drive_blocks(rng, 2 * R, SpanTimer())
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    data = jax.profiler.ProfileData.from_file(path)
    names = [e.name for p in data.planes if p.name.startswith("/host:")
             for ln in p.lines for e in ln.events
             if e.name.startswith("repro.")]
    for span in ("stage", "dispatch", "wait", "fetch"):
        assert names.count("repro." + span) == 2, span
    assert names.count("repro.stage_ahead") == 2 - 1
    assert "repro.pack" in names and "repro.unpack" in names


class _SpanLog:
    """A span object for the block driver that logs each span's entry
    and exit, in order, into a list it shares with the stage function."""

    def __init__(self, log):
        self.log = log

    @contextlib.contextmanager
    def span(self, name):
        self.log.append(("enter", name))
        try:
            yield
        finally:
            self.log.append(("exit", name))


def _logged_problem(rng, rounds):
    """The tiny problem's loop and state, with distinct batches for
    every round, so a block staged with the wrong rounds shows."""
    loss, params, _ = _problem(rng)
    copt, sopt = _opts()
    loop = make_fl_loop(loss, copt, sopt, params_like=params,
                        num_rounds=rounds, rounds_per_call=R, flat="xla")
    pool = [{"A": rng.normal(size=(C, K, 4, D)).astype(np.float32),
             "b": rng.normal(size=(C, K, 4)).astype(np.float32)}
            for _ in range(rounds)]

    def block(round0, n):
        return {k: jnp.asarray(np.stack([pool[round0 + i][k]
                                         for i in range(n)]))
                for k in pool[0]}

    return loop, init_fl_state(params, sopt), block


def _drive_logged(loop, state, block, rounds, log=None, **args):
    """``rounds`` rounds through the block driver with a logging span
    object and a logging ``stage_block``; returns the final state, the
    metric rows and the log."""
    from repro.launch.train import _run_fused
    log = [] if log is None else log
    rows = []

    def stage_block(round0, n):
        log.append(("stage_block", round0, n))
        return block(round0, n), None

    args = types.SimpleNamespace(**{"rounds_per_call": R,
                                    "ckpt_dir": None,
                                    "ckpt_every": 10 ** 9,
                                    "profile": 0, **args})
    state = _run_fused(args, loop, state, rounds, stage_block,
                       lambda t, row: rows.append(row),
                       spans=_SpanLog(log))
    return state, rows, log


def _where(log, entry):
    return [i for i, e in enumerate(log) if e == entry]


@pytest.mark.parametrize("rounds", [3 * R, 2 * R + 1])
def test_run_fused_stages_one_block_ahead(rng, rounds):
    """``stage_block`` runs once a block, in round order, never past
    ``rounds`` (a short last block gets its own length); every block
    but the first is staged after the dispatch of the block before it
    and before the wait for it, inside a ``stage_ahead`` span."""
    loop, state, block = _logged_problem(rng, rounds)
    _, rows, log = _drive_logged(loop, state, block, rounds)
    assert len(rows) == rounds
    blocks = [(r0, min(R, rounds - r0)) for r0 in range(0, rounds, R)]
    calls = [i for i, e in enumerate(log) if e[0] == "stage_block"]
    assert [log[i][1:] for i in calls] == blocks
    dispatched = _where(log, ("exit", "dispatch"))
    waited = _where(log, ("enter", "wait"))
    assert len(dispatched) == len(waited) == len(blocks)
    assert calls[0] < _where(log, ("enter", "dispatch"))[0]
    assert log[calls[0] - 1] == ("enter", "stage")
    for k in range(1, len(blocks)):
        assert dispatched[k - 1] < calls[k] < waited[k - 1], k
        assert log[calls[k] - 2:calls[k]] == [("enter", "stage"),
                                              ("enter", "stage_ahead")]
    assert len(_where(log, ("enter", "stage_ahead"))) == len(blocks) - 1


def test_run_fused_matches_serial_blocks_bit_exact(rng):
    """Staging ahead changes no number: params and every metric row
    equal those of the jitted loop called block by block, serially, on
    the same batches (a short last block included)."""
    rounds = 2 * R + 1
    loop, state, block = _logged_problem(rng, rounds)
    got, rows, _ = _drive_logged(loop, state, block, rounds)
    jloop = jax.jit(loop, donate_argnums=0)
    fs = flatten_fl_state(state, loop.layout)
    want = []
    for r0 in range(0, rounds, R):
        n = min(R, rounds - r0)
        fs, mets = jloop(fs, block(r0, n))
        mets = jax.device_get(mets)
        want += [{k: v[r] for k, v in mets.items()} for r in range(n)]
    _assert_trees_equal(got.params, unflatten_fl_state(fs, loop.layout)
                        .params)
    assert len(rows) == len(want) == rounds
    for a, b in zip(rows, want):
        assert a.keys() == b.keys()
        for k in a:
            np.testing.assert_array_equal(np.asarray(a[k]),
                                          np.asarray(b[k]), err_msg=k)


def test_run_fused_profiles_one_block_with_staging_ahead(rng, tmp_path,
                                                         monkeypatch):
    """``--profile`` lowers, compiles and traces exactly one block: no
    staging runs inside its trace, and the block after it is staged
    once the trace has ended, outside ``stage_ahead``."""
    import repro.telemetry as telemetry
    rounds = 3 * R
    loop, state, block = _logged_problem(rng, rounds)
    real = telemetry.trace_block
    log = []

    def logged_trace(fn, logdir):
        log.append(("enter", "trace_block"))
        try:
            return real(fn, logdir)
        finally:
            log.append(("exit", "trace_block"))

    monkeypatch.setattr(telemetry, "trace_block", logged_trace)
    _, rows, _ = _drive_logged(loop, state, block, rounds, log=log,
                               profile=R + 1, profile_dir=str(tmp_path))
    assert len(rows) == rounds
    calls = [i for i, e in enumerate(log) if e[0] == "stage_block"]
    assert [log[i][1:] for i in calls] == [(0, R), (R, R), (2 * R, R)]
    assert len(_where(log, ("enter", "compile"))) == 1
    [start] = _where(log, ("enter", "trace_block"))
    [end] = _where(log, ("exit", "trace_block"))
    assert not [i for i in calls if start < i < end]
    # block 1 (rounds R+1..2R) is the profiled one: it was staged ahead
    # while block 0 ran, and block 2 after the trace, as a plain stage
    assert calls[2] > end
    assert log[calls[2] - 1] == ("enter", "stage")
    assert log[calls[1] - 1] == ("enter", "stage_ahead")
    assert len(_where(log, ("enter", "stage_ahead"))) == 1


# ----------------------------------------------------- spec + registry
def test_resolve_telemetry_forms():
    assert not resolve_telemetry(None).enabled
    assert not resolve_telemetry(False).enabled
    assert resolve_telemetry(True).enabled
    spec = TelemetrySpec(enabled=True, eta_bins=8)
    assert resolve_telemetry(spec) is spec
    with pytest.raises(ValueError):
        resolve_telemetry("yes")
    edges = TelemetrySpec(eta_bins=8).eta_edges()
    assert len(edges) == 9
    assert edges[0] == 0.0 and np.isinf(edges[-1])


def test_round_telemetry_disabled_is_empty(rng):
    assert round_telemetry(TelemetrySpec(), jnp.ones(4),
                           jnp.ones((4, 2))) == {}


def test_schema_registry_roundtrip():
    """Every registered summary reduction is valid; the generated
    markdown table carries every metric; report names the launch
    drivers rely on stay registered."""
    specs = schema.specs()
    assert len(specs) >= 25
    table = schema.markdown_table()
    for s in specs:
        assert f"`{s.name}`" in table
        for _, red in s.summaries:
            assert red in ("mean", "sum", "min", "max")
    for name in ("loss", "eta_mean", "cohort_ids", "eta_hist",
                 "loss_deciles", "wire_bytes", "eta_clip_rate"):
        assert schema.get(name) is not None
    assert schema.is_scalar("loss")
    assert not schema.is_scalar("eta_hist")


def test_warn_unregistered_warns_once():
    schema._warned.discard("zz_bogus_metric")
    with pytest.warns(UserWarning, match="zz_bogus_metric"):
        schema.warn_unregistered("zz_bogus_metric", producer="test")
    import warnings as w
    with w.catch_warnings():
        w.simplefilter("error")         # second call must NOT warn
        schema.warn_unregistered("zz_bogus_metric", producer="test")


def test_scenario_stats_routes_unregistered(rng):
    """launch/train._ScenarioStats stores EVERY metric (the old KEYS
    whitelist silently dropped unknown names), warning once."""
    from repro.launch.train import _ScenarioStats
    schema._warned.discard("zz_new_metric")
    stats = _ScenarioStats(None, num_clients=4)
    with pytest.warns(UserWarning, match="zz_new_metric"):
        stats.update(np.asarray([0, 1]),
                     {"stale_mean": 1.5, "zz_new_metric": 2.0,
                      "eta_hist": np.asarray([1.0, 2.0])})
    assert stats.metrics[0]["zz_new_metric"] == 2.0
    assert stats.metrics[0]["stale_mean"] == 1.5
    np.testing.assert_array_equal(stats.metrics[0]["eta_hist"],
                                  [1.0, 2.0])
    rep = stats.report()
    assert rep["stale_mean"] == 1.5
    assert rep["eta_hist"] == [1.0, 2.0]


# ------------------------------------------------------------ artifacts
def test_event_log_header_and_flush(tmp_path):
    path = tmp_path / "events.jsonl"
    cfg = {"task": "easy", "rounds": 4}
    with EventLog(str(path), config=cfg) as ev:
        # header is on disk BEFORE any flush (crash-visible metadata)
        header, events = load_events(str(path))
        assert header["kind"] == "header" and events == []
        assert header["config_hash"] == config_hash(cfg)
        ev.emit("round", t=0, loss=jnp.float32(1.5),
                eta_hist=np.arange(3, dtype=np.float32))
        assert ev.flush() == 1
        ev.emit("round", t=1, loss=0.5)
    header, events = load_events(str(path))
    assert [e["kind"] for e in events] == ["round", "round"]
    assert events[0]["loss"] == 1.5            # np scalars -> json floats
    assert events[0]["eta_hist"] == [0.0, 1.0, 2.0]
    assert ev.events_written == 2
    for line in path.read_text().splitlines():
        json.loads(line)                       # every line valid JSON


def test_event_log_rejects_headerless(tmp_path):
    p = tmp_path / "bad.jsonl"
    p.write_text('{"kind": "round", "t": 0}\n')
    with pytest.raises(ValueError):
        load_events(str(p))


def test_span_timer():
    st = SpanTimer()
    with st.span("pack"):
        pass
    with st.span("pack"):
        pass
    with st.span("stage"):
        time.sleep(0.01)
    s = st.summary()
    assert s["pack"]["n"] == 2 and s["pack"]["s"] >= 0.0
    assert s["stage"]["n"] == 1 and s["stage"]["s"] >= 0.01
    assert "pack" in str(st) and "stage" in str(st)


def test_static_telemetry_counts_collectives(rng):
    from repro.telemetry import static_telemetry
    loss, params, batches = _problem(rng)
    copt, sopt = _opts()
    loop = make_fl_loop(loss, copt, sopt, params_like=params,
                        num_rounds=10, rounds_per_call=R, flat="pallas",
                        telemetry=True)
    fst = flatten_fl_state(init_fl_state(params, sopt), loop.layout)
    reset_kernel_launches()
    lowered = jax.jit(loop).lower(fst, batches)
    snap = kernel_launch_snapshot()
    row = static_telemetry(lowered.compile(), rounds=R, launches=snap)
    assert row["rounds"] == R
    # the round scan body traces the Δ-SGD pair once for the whole block
    assert row["pallas_launches"]["delta_sgd/batched_norms"] == 1
    assert row["pallas_launches_per_round"]["delta_sgd/batched_norms"] \
        == 1 / R
    assert any(k.startswith("telemetry/") for k in row["pallas_launches"])


# ------------------------------------------------------- report layer
def test_report_tables_guard_missing_columns():
    from repro.launch.report import (dryrun_table, roofline_table,
                                     scenario_table)
    assert "| mlp | - |" in dryrun_table([{"arch": "mlp"}])
    assert roofline_table([{"mesh": "16x16"}]).count("\n") == 1
    out = scenario_table([{"scenario": "x"}])
    assert "| x | - |" in out


def test_scenario_summary_registry_driven():
    from repro.launch.report import scenario_summary
    mets = [{"stale_mean": 1.0, "wire_bytes": 100.0,
             "eta_hist": [0.0, 2.0, 1.0], "loss_deciles": [1.0, 2.0]},
            {"stale_mean": 3.0, "wire_bytes": 300.0,
             "eta_hist": [1.0, 0.0, 1.0], "loss_deciles": [3.0, 4.0]}]
    s = scenario_summary("sync_iid", [[0, 1], [1, 2]], 4, mets)
    assert s["stale_mean"] == 2.0
    assert s["wire_bytes_round"] == 200.0 and s["wire_bytes_total"] == 400.0
    assert s["eta_hist"] == [1.0, 2.0, 2.0]          # summed over rounds
    assert s["loss_deciles"] == [2.0, 3.0]           # averaged
    assert len(s["eta_hist_edges"]) == 4
    assert s["eta_hist_edges"][0] == 0.0


def test_eta_hist_render():
    from repro.launch.report import eta_hist_render
    edges = TelemetrySpec(eta_bins=4).eta_edges()
    out = eta_hist_render([1, 0, 2, 5], edges)
    assert "8 client-rounds" in out and "#####" in out
    assert eta_hist_render([0, 0], [0.0, 1.0, np.inf]).startswith("(empty")
