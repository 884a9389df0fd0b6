"""Operation and byte counts against hand counts, and the readers that
turn them into shares of the chip's peaks."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

from harness.common import load_cell, load_module, peaks_for  # noqa: E402

MS = 1_000_000


def test_whisper_forward_by_hand():
    cell = load_cell("lm.whisper_tiny.fedtune4")
    cfg = cell.config
    # encoder layer over 1500 frames: q,k,v,o 1769.5 M, scores and sum
    # 3456 M, MLP 3538.9 M; decoder layer over 448 tokens: self q,k,v,o
    # 528.5 M, causal scores 154.1 M, cross q,o 264.2 M, cross k,v over
    # the frames 884.7 M, cross scores 1032.2 M, MLP 1056.9 M; head
    # 448 x 384 x 51865 x 2 = 17844.9 M
    enc = 1769.472e6 + 3456e6 + 3538.944e6
    dec = 528.482e6 + 154.140e6 + 264.241e6 + 884.736e6 + 1032.192e6 \
        + 1056.964e6
    head = 17844.879e6
    want = 4 * enc + 4 * dec + head
    got = cell.reference.forward_flops(cfg, 448, 1500, 1)
    assert got == pytest.approx(want, rel=1e-5)
    assert got == pytest.approx(68.6e9, rel=0.002)
    assert cell.reference.train_flops(cfg, 448, 1500, 2) == \
        pytest.approx(6 * got)


def test_round_counts_of_the_training_cells():
    from drivers import fedtune
    lm = load_cell("lm.whisper_tiny.fedtune4")
    fwd = lm.reference.forward_flops(lm.config, 448, 1500, 1)
    # 4 clients x 2 local steps, forward + backward
    assert fedtune.flops_per_round(lm) == pytest.approx(3 * fwd * 8)
    n = 56_458_752
    # per local step: norms read G, G_prev; apply reads P, G, writes P
    assert fedtune.pair_bytes_per_round(lm, n) == 5 * 4 * n * 4 * 2


def test_fleet_round_counts():
    """The fleet cell's round at C x K_max client steps: 50 clients x 7
    local steps of the 32-64-64-10 MLP over batches of 64."""
    from types import SimpleNamespace

    from drivers import fleet
    from fleet_pending import fleet_cell
    cell = fleet_cell()
    ref = cell.reference
    # 32*64 + 64*64 + 64*10 = 6784 multiply-adds an example, x 2 x 3
    assert ref.train_flops(cell.config, 64) == 6 * 64 * 6784
    assert ref.num_params(cell.config) == 2112 + 4160 + 650
    b = SimpleNamespace(C=50, K=7, b=64, cfg=cell.config, ref=ref,
                        loop=SimpleNamespace(
                            layout=SimpleNamespace(padded_size=7040)))
    got = fleet.counts(b)
    assert got["flops_per_round"] == 6 * 64 * 6784 * 350
    assert got["pair_bytes_per_round"] == 5 * 4 * 6922 * 350
    assert got["pair_shape"] == "f32[50,55,128]"


def reader(name):
    return load_module(os.path.join(BENCH, "metrics", name + ".py")).read


def test_shares_of_the_peaks():
    peaks = peaks_for("TPU v5 lite")
    kern = ('%closed_call.{} = f32[4,8,128] custom-call(f32[4,8,128] %p),'
            ' custom_call_target="tpu_custom_call"')
    tr = {"devices": {"0": {"ops": [[kern.format(1), 0, 4 * MS],
                                    [kern.format(2), 4 * MS, 6 * MS],
                                    ["%fusion = f32[8] fusion(f32[8] %x)",
                                     10 * MS, 80 * MS]],
                            "modules": []}},
          "host": [], "window": [0, 100 * MS]}
    counts = {"rounds": 2, "flops_per_round": 1.97e12,
              "pair_bytes_per_round": 819e9 * 0.004,
              "pair_shape": "f32[4,8,128]"}
    ctx = {"trace": tr, "counts": counts, "peaks": peaks, "chips": 1}
    # 2 rounds x 1.97 TFLOP in 0.1 s over 197 TFLOP/s = 20 %
    assert reader("mfu.train")(ctx) == pytest.approx(20.0)
    # 2 x 4 ms of HBM traffic at peak, in 10 ms of kernel time = 80 %
    assert reader("delta_sgd_roofline")(ctx) == pytest.approx(80.0)
    assert reader("delta_sgd_ms_per_round")(ctx) == pytest.approx(5.0)
    assert reader("idle_share.train")(ctx) == pytest.approx(10.0)
    # a trace without the pair's calls: the readers say nothing
    ctx["counts"] = dict(counts, pair_shape="f32[4,9,128]")
    assert reader("delta_sgd_roofline")(ctx) is None
    assert reader("delta_sgd_ms_per_round")(ctx) is None


def test_a_device_missing_from_the_peaks_is_an_error():
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")
