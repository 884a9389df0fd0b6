"""chip_smoke.py on the CPU: its TPU gate, and each of its phases at
``--reduced`` size (the chip runs them at full width; see
``python chip_smoke.py``). The Δ-SGD kernel pair is steered onto its
Pallas path here, in interpret mode, since the fleet phase checks that
the pair was built."""
import json
import os
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import chip_smoke as cs  # noqa: E402

sys.path.remove(ROOT)

needs4 = pytest.mark.skipif(jax.device_count() < 4,
                            reason="needs >= 4 devices "
                                   "(XLA_FLAGS=--xla_force_host_platform"
                                   "_device_count=8)")


@pytest.fixture
def pallas_pair(monkeypatch):
    import repro.launch.train as train
    monkeypatch.setattr(train, "flat_backend", lambda: "pallas")


@pytest.fixture(scope="module")
def lm_out(tmp_path_factory):
    """The lm phase at reduced size; serve reads its checkpoint."""
    out = str(tmp_path_factory.mktemp("chip_smoke"))
    row = cs.phase_lm(out, cs.LM_ARGS + [
        "--reduced", "--layers", "2", "--d-model", "256", "--seq", "16",
        "--batch", "2", "--clients-per-round", "2", "--rounds", "2"])
    return out, row


def test_gate_exits_before_any_phase_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert cs.main([]) == 1
    out = capsys.readouterr().out.strip().splitlines()
    assert out[0].startswith("device: ")
    assert not any(line.startswith("{") for line in out), out


def test_fleet_phase_reduced(tmp_path, pallas_pair):
    from repro.telemetry import reset_kernel_launches
    reset_kernel_launches()
    row = cs.phase_fleet(str(tmp_path), cs.FLEET_ARGS + [
        "--rounds", "2", "--rounds-per-call", "2",
        "--num-registered", "2000"])
    assert row["rounds"] == 2
    pair = row["kernels"]["launches"]["delta_sgd"]
    assert pair["batched_norms"] >= 1 and pair["batched_apply"] >= 1
    json.dumps(row)


def test_lm_phase_reduced(lm_out):
    out, row = lm_out
    assert row["rounds"] == 2 and row["ckpt_step"] == 2
    assert os.path.isdir(row["ckpt_dir"])


def test_serve_phase_reduced(lm_out):
    out, lm = lm_out
    row = cs.phase_serve(out, lm["ckpt_dir"], cs.SERVE_ARGS + [
        "--reduced", "--prompt-len", "8", "--gen", "4"])
    assert row["requests"] == 8 and row["isolated_match"]


@needs4
def test_mesh_phase_on_four_devices():
    row = cs.phase_mesh(jax.devices()[:4], clients=8, batch=8)
    assert row["devices"] == 4
    assert row["max_abs_param_err"] <= cs.MESH_TOL


def test_compile_cache_dir(monkeypatch):
    from repro.launch import compile_cache as cc
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", prev)
        assert cc.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert cc.enable_compile_cache() == cc.DEFAULT_DIR
        assert cc.DEFAULT_DIR == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == cc.DEFAULT_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
