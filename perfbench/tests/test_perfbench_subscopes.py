"""Device time by the model's own scopes (``harness/subscopes.py``): the
innermost-part rule, the reduction and the three part readers and the
experts' roofline on a hand-made trace with known answers, what they
read on a trace of a program without the parts, and the Kanana share's
operation counts against hand counts."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

from harness import scopes, subscopes  # noqa: E402
from harness.common import load_cell, load_module, peaks_for  # noqa: E402

MS = 1_000_000
READERS = ("mla_ms_per_round", "moe_route_ms_per_round",
           "moe_experts_ms_per_round")
GRAD = "jit(loop_fn)/while/body/closed_call/client_grad"


def reader(name):
    return load_module(os.path.join(BENCH, "metrics", name + ".py"))


def test_innermost_part():
    assert subscopes.part_of(GRAD + "/vmap(jvp())/mla/dot_general") == "mla"
    assert subscopes.part_of(
        GRAD + "/vmap(transpose(jvp()))/checkpoint/moe_experts/"
        "jit(_where)/select_n") == "moe_experts"
    assert subscopes.part_of(
        "jit(f)/transpose(jvp(moe_route))/gather") == "moe_route"
    assert subscopes.part_of(GRAD + "/moe_route/moe_combine/add") == \
        "moe_combine"
    assert subscopes.part_of(GRAD + "/vmap(jvp())/dot_general") is None
    assert subscopes.part_of("") is None
    # the round body's layer is still client_grad for every part
    assert scopes.layer_of(GRAD + "/vmap(jvp())/mla/dot_general") == \
        "client_grad"


def hand_trace():
    """One device over a 10 ms window, 2 rounds: attention 0-3 ms and its
    backward 5-7 ms, routing 3-3.5 ms, the experts' kernel 3.5-4.5 ms
    with an overlapping select 4-5 ms, the combine 5-5.5 ms (inside the
    attention backward's interval: it still counts as combine), a dense
    MLP op with no part 8-9 ms."""
    ops = [["%fusion.1 = fusion()", 0, 3 * MS, GRAD + "/mla/dot_general"],
           ["%fusion.2 = fusion()", 5 * MS, 2 * MS,
            GRAD + "/transpose(jvp(mla))/dot_general"],
           ["%sort.1 = sort()", 3 * MS, MS // 2, GRAD + "/moe_route/sort"],
           ["%gmm.1 = custom-call()", 3 * MS + MS // 2, MS,
            GRAD + "/moe_experts/pallas_call"],
           ["%select.1 = select()", 4 * MS, MS,
            GRAD + "/checkpoint/moe_experts/select_n"],
           ["%scatter.1 = scatter()", 5 * MS, MS // 2,
            GRAD + "/moe_combine/scatter-add"],
           ["%fusion.3 = fusion()", 8 * MS, MS, GRAD + "/dot_general"]]
    return {"devices": {"0": {"ops": [e[:3] for e in ops],
                              "scopes": [e[3] for e in ops]}},
            "host": [["bench.window", 0, 10 * MS]], "window": [0, 10 * MS]}


def test_part_time_on_a_hand_made_trace():
    tr = hand_trace()
    assert subscopes.part_time_ns(tr, "mla") == pytest.approx(5 * MS)
    assert subscopes.part_time_ns(tr, "moe_route") == pytest.approx(MS / 2)
    # the kernel and its select overlap 4-4.5: counted once
    assert subscopes.part_time_ns(tr, "moe_experts") == \
        pytest.approx(1.5 * MS)
    assert subscopes.part_time_ns(tr, "moe_combine") == \
        pytest.approx(MS / 2)
    assert subscopes.part_time_ns(tr, "shared_experts") is None
    # every part sits inside client_grad
    assert scopes.scope_time_ns(tr, "client_grad") == pytest.approx(8 * MS)


def test_readers_and_the_experts_roofline():
    peaks = peaks_for("TPU v5 lite")
    flops = 0.5e-3 * peaks["bf16_flops_per_s"]     # 0.5 ms at the peak
    ctx = {"trace": hand_trace(), "peaks": peaks,
           "counts": {"rounds": 2, "expert_flops_per_round": flops}}
    got = {n: reader(n).read(ctx) for n in READERS}
    assert got == pytest.approx({"mla_ms_per_round": 2.5,
                                 "moe_route_ms_per_round": 0.25,
                                 "moe_experts_ms_per_round": 0.75})
    # 0.5 ms of work at the peak in 0.75 ms a round
    assert reader("moe_experts_roofline").read(ctx) == \
        pytest.approx(100 * 0.5 / 0.75)


def test_readers_read_nothing_without_the_parts(monkeypatch, tmp_path):
    """A program without the model's scopes (whisper's, or the parent
    of the change that named them): every reader says None and none
    raises."""
    monkeypatch.setattr(scopes, "TRACE_ROOT", str(tmp_path))
    with open(os.path.join(HERE, "data", "lm_scoped_slice.json")) as f:
        tr = json.load(f)["trace"]
    ctx = {"trace": tr, "peaks": peaks_for("TPU v5 lite"),
           "counts": {"rounds": 1, "expert_flops_per_round": 1e9}}
    names = READERS + ("moe_experts_roofline",)
    assert {n: reader(n).read(ctx) for n in names} == dict.fromkeys(names)
    ctx["trace"] = dict(tr, window=None)
    assert {n: reader(n).read(ctx) for n in names} == dict.fromkeys(names)


def test_kanana_forward_by_hand():
    """One forward of 4 x 2048 tokens at the published widths with
    12,288 pairs routed to the held experts (even routing: 8192 tokens
    x 6 choices x 4 layers x 8 / 128)."""
    cell = load_cell("lm.kanana2_30b_a3b.silo8k")
    ref, cfg = cell.reference, cell.config
    T, S, B = 8192, 2048, 4
    # per layer: q 2048x6144, latent 2048x576, k_nope and v 512x4096
    # each, output 4096x2048; causal scores and sum, 32 heads x (192 +
    # 128) at half the 2048 x 2048 square, per sequence
    attn = 2 * T * (2048 * 6144 + 2048 * 576 + 2 * 512 * 4096
                    + 4096 * 2048) + 2 * B * 32 * 320 * S * S / 2
    dense = 2 * T * 3 * 2048 * 6144
    # router over all 128 experts, shared experts 3 x 2048 x 1536
    per_moe = 2 * T * (2048 * 128 + 3 * 2048 * 1536)
    experts = 12288 * 2 * 3 * 2048 * 768
    head = 2 * T * 2048 * 16032
    want = 5 * attn + dense + 4 * per_moe + experts + head
    assert ref.expected_pairs(cfg, T) == 12288
    assert ref.forward_flops(cfg, S, B) == pytest.approx(want, rel=1e-12)
    assert ref.forward_flops(cfg, S, B) == pytest.approx(4.925e12, rel=1e-3)
    # the routed-pair counter replaces the even-routing guess
    assert ref.forward_flops(cfg, S, B, pairs=0) == \
        pytest.approx(want - experts, rel=1e-12)
    assert ref.train_flops(cfg, S, B) == \
        pytest.approx(3 * ref.forward_flops(cfg, S, B))


def test_kanana_round_counts():
    from drivers import fedtune, fedtune_lm
    cell = load_cell("lm.kanana2_30b_a3b.silo8k")
    ref, cfg = cell.reference, cell.config
    # 1 client x 2 local steps, each step's pairs half the round's
    got = fedtune_lm.flops_per_round(cell, 30000)
    assert got == pytest.approx(2 * ref.train_flops(cfg, 2048, 4,
                                                    pairs=15000))
    n = 425_354_240
    assert fedtune.pair_bytes_per_round(cell, n) == 5 * 1 * n * 4 * 2
    assert ref.expert_flops(cfg, 1) == 6 * 2048 * 768
