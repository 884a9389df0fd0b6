"""A run with the timed path broken underneath comes out not correct.

Each test drives the rest of a run (set-up, window, check; the look for
a chip is skipped) at a size the CPU holds, with the cell's own limits:
once sound, and once for each fault the cell can have: a step that
returns its state unchanged, half of each batch left out of the loss."""
import os
import sys
import types

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import run as bench_run  # noqa: E402
from harness.common import load_cell  # noqa: E402

TINY = {"d_model": 64, "decoder_layers": 2, "encoder_layers": 2,
        "decoder_attention_heads": 2, "encoder_attention_heads": 2,
        "decoder_ffn_dim": 128, "encoder_ffn_dim": 128, "vocab_size": 500,
        "max_source_positions": 24}


@pytest.fixture
def isolated_cache(tmp_path, monkeypatch):
    """The run's persistent compile cache in a scratch directory; the
    process's cache settings are put back afterwards."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    yield tmp_path
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def tiny_cell():
    """The lm cell at a size the CPU holds."""
    cell = load_cell("lm.whisper_tiny.fedtune4")
    cell.config = dict(cell.config, **TINY)
    cell.mix = dict(cell.mix, seq=12, pool_rounds=3)
    return cell


def measure(tmp, seconds=1.0):
    import jax
    cell = tiny_cell()
    args = types.SimpleNamespace(seed=2 ** 31 + 11, seconds=seconds,
                                 trace=0)
    line = bench_run.measure(cell, args, jax.devices()[:cell.chips],
                             trace_root=str(tmp))
    return line["correct"], {c["name"]: c["value"]
                             for c in line["compared"]}


def test_training_sound(isolated_cache):
    ok, nums = measure(isolated_cache)
    assert ok, nums


def test_training_state_unchanged(isolated_cache, monkeypatch):
    import repro.core as core
    orig = core.make_fl_loop

    def broken(*a, **k):
        loop = orig(*a, **k)

        def frozen(carry, data, client_weights=None, arena=None):
            _, mets = loop(carry, data, client_weights, arena)
            return carry, mets
        frozen.__dict__.update(loop.__dict__)
        return frozen

    monkeypatch.setattr(core, "make_fl_loop", broken)
    ok, nums = measure(isolated_cache)
    assert not ok and nums["change"] == pytest.approx(1.0)


def test_training_half_batch(isolated_cache, monkeypatch):
    from repro.models import model as model_mod

    def half_loss(self, params, batch, *, use_pallas=False):
        logits, aux = self.apply(params, batch)
        half = batch["labels"].shape[-1] // 2
        ce = model_mod._ce(logits[:, :half], batch["labels"][:, :half])
        return ce + aux, {"ce": ce, "aux": aux}

    monkeypatch.setattr(model_mod.Model, "loss", half_loss)
    ok, nums = measure(isolated_cache)
    assert not ok, nums
