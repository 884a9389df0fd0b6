"""Compile the hot path for a TPU v5e that is described, not attached.

Every Pallas kernel of the seven namespaces, at the widths the main path
runs them, and one whisper-tiny fused training block at the size
``chip_smoke.py`` trains it, go through the TPU compiler here on the
CPU (``jax.experimental.topologies``). Nothing runs: a compile that
passes is what the chip's compiler accepts — tiling, VMEM and lowering
rules that interpret mode never checks — and ``memory_analysis()`` is
its estimate of one program's device memory.

The topology is described inside a module fixture, never at import:
only the test worker that is given this file loads the TPU compiler.
The persistent compilation cache is off around these compiles (an entry
written for a described chip cannot be read back without one).
"""
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V5E_HBM = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    mp = pytest.MonkeyPatch()
    # the TPU compiler would otherwise write its logs under /tmp
    mp.setenv("TPU_LOG_DIR", "disabled")
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        mp.undo()


@pytest.fixture(scope="module")
def tpu_compile(topo):
    """``compile(fn, *shapes)`` for one v5e chip, kernels compiled (not
    interpreted) and the flat engine on its TPU backend."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    import repro.kernels as rk
    one_chip = SingleDeviceSharding(topo.devices[0])
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    mp = pytest.MonkeyPatch()
    # the code under test asks the platform; the described chip is not it
    mp.setattr(rk, "on_tpu", lambda: True)

    def compile_(fn, *shapes, **jit_kw):
        args = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), shapes)
        return jax.jit(fn, **jit_kw).lower(*args).compile()

    yield compile_
    mp.undo()
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _slab(C, N):
    """The Δ-SGD pair's operand shape: the (C, N/128, 128) client slab."""
    return (C, N // 128, 128)


def _padded_n(params_like):
    from repro.core import flat as flatlib
    return flatlib.layout_of(params_like).padded_size


def _whisper():
    from repro.configs import get_config
    from repro.models import build_model
    cfg = get_config("whisper-tiny")
    model = build_model(cfg, jnp.float32)
    return cfg, model, jax.eval_shape(model.init, jax.random.key(0))


def _lm_args():
    """chip_smoke.py's lm phase, parsed by the trainer's own parser."""
    sys.path.insert(0, ROOT)
    try:
        import chip_smoke
    finally:
        sys.path.remove(ROOT)
    from repro.launch.train import build_parser
    return build_parser().parse_args(chip_smoke.LM_ARGS)


def _fleet_sizes():
    """(cohort, padded N) of the fleet phase: the fleet_zipf preset's
    cohort over the paper task's MLP."""
    from repro.configs.paper_tasks import MLP_SMALL
    from repro.federation import get_scenario
    from repro.models.small import make_small_model
    scn = get_scenario("fleet_zipf")
    C = round(scn.registered_hint * scn.participation_hint)
    init_fn, _ = make_small_model(MLP_SMALL)
    return C, _padded_n(jax.eval_shape(init_fn, jax.random.key(0)))


def _kernel_cases():
    from repro.kernels.compress import compress as ck
    from repro.kernels.delta_sgd import delta_sgd as dk
    from repro.kernels.flash_attention.flash_attention import \
        flash_attention
    from repro.kernels.grouped_matmul import ops as gk
    from repro.kernels.mamba2_scan.mamba2_scan import ssd_chunks
    from repro.kernels.robust_agg import robust_agg as rk
    from repro.kernels.telemetry import telemetry as tk

    _, _, wparams = _whisper()
    Cw, Nw = _lm_args().clients_per_round, _padded_n(wparams)
    Cf, Nf = _fleet_sizes()
    ip = dict(interpret=False)
    fa = lambda q, k, v: flash_attention(q, k, v, causal=True, **ip)
    # zamba2-7b's SSD widths: d_inner 7168 over 64-wide heads, state 64
    H, P, N, S = 112, 64, 64, 1024
    # kanana-2's held experts: 4 x 2048 tokens x 6 choices, 8 held of
    # width 768 over d 2048, and the trailing group of unheld pairs
    T, D, F, E = 4 * 2048 * 6, 2048, 768, 8

    def gmm(x, w, g):
        return gk.grouped_matmul(x, w, g, pallas=True, **ip)
    return {
        "delta_sgd.batched_norms@whisper": (
            lambda g, gp: dk.batched_norms(g, gp, **ip),
            _sds(_slab(Cw, Nw)), _sds(_slab(Cw, Nw))),
        "delta_sgd.batched_apply@whisper": (
            lambda p, g, e, v: dk.batched_apply(p, g, e, valid=v, **ip),
            _sds(_slab(Cw, Nw)), _sds(_slab(Cw, Nw)), _sds((Cw,)),
            _sds((Cw,), jnp.bool_)),
        "delta_sgd.batched_norms@fleet": (
            lambda g, gp: dk.batched_norms(g, gp, **ip),
            _sds(_slab(Cf, Nf)), _sds(_slab(Cf, Nf))),
        "delta_sgd.batched_apply@fleet": (
            lambda p, g, e, v: dk.batched_apply(p, g, e, valid=v, **ip),
            _sds(_slab(Cf, Nf)), _sds(_slab(Cf, Nf)), _sds((Cf,)),
            _sds((Cf,), jnp.bool_)),
        "delta_sgd.norms@whisper": (
            lambda g, gp: dk.norms(g, gp, **ip), _sds((Nw,)), _sds((Nw,))),
        "delta_sgd.apply_update@whisper": (
            lambda p, g: dk.apply_update(p, g, 0.1, **ip),
            _sds((Nw,)), _sds((Nw,))),
        "compress.quantize_int8@whisper": (
            lambda x: ck.quantize_int8(x, **ip), _sds((Cw, Nw))),
        "compress.dequantize_int8@whisper": (
            lambda q, s: ck.dequantize_int8(q, s, **ip),
            _sds((Cw, Nw), jnp.int8), _sds((Cw, Nw // 128))),
        "compress.topk_mask@whisper": (
            lambda x: ck.topk_mask(x, 13, **ip), _sds((Cw, Nw))),
        "robust_agg.batched_trimmed_mean@whisper": (
            lambda x: rk.batched_trimmed_mean(x, 1, **ip), _sds((Cw, Nw))),
        "telemetry.lane_histogram@fleet": (
            lambda x: tk.lane_histogram(x, jnp.linspace(0.0, 1.0, 17), **ip),
            _sds((Cf,))),
        "telemetry.lane_quantiles@fleet": (
            lambda x: tk.lane_quantiles(x, **ip), _sds((Cf,))),
        "flash_attention@whisper_decoder_448": (
            fa, *(_sds((1, 448, 6, 64)),) * 3),
        "flash_attention@whisper_encoder_1500": (
            fa, *(_sds((1, 1500, 6, 64)),) * 3),
        "grouped_matmul.forward@kanana": (
            gmm, _sds((T, D)), _sds((E, D, F)), _sds((E + 1,), jnp.int32)),
        "grouped_matmul.backward@kanana": (
            lambda x, w, g, dy: jax.vjp(lambda a, b: gmm(a, b, g),
                                        x, w)[1](dy),
            _sds((T, F)), _sds((E, F, D)), _sds((E + 1,), jnp.int32),
            _sds((T, D))),
        "mamba2_scan.ssd_chunks@zamba2": (
            lambda x, dt, dA, b, c: ssd_chunks(x, dt, dA, b, c, **ip),
            _sds((1, S, H, P)), _sds((1, S, H)), _sds((1, S, H)),
            _sds((1, S, H, N)), _sds((1, S, H, N))),
    }


KERNELS = [
    "delta_sgd.batched_norms@whisper", "delta_sgd.batched_apply@whisper",
    "delta_sgd.batched_norms@fleet", "delta_sgd.batched_apply@fleet",
    "delta_sgd.norms@whisper", "delta_sgd.apply_update@whisper",
    "compress.quantize_int8@whisper", "compress.dequantize_int8@whisper",
    "compress.topk_mask@whisper", "robust_agg.batched_trimmed_mean@whisper",
    "telemetry.lane_histogram@fleet", "telemetry.lane_quantiles@fleet",
    "flash_attention@whisper_decoder_448",
    "flash_attention@whisper_encoder_1500",
    "mamba2_scan.ssd_chunks@zamba2",
    "grouped_matmul.forward@kanana", "grouped_matmul.backward@kanana",
]


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(name, tpu_compile):
    fn, *shapes = _kernel_cases()[name]
    compiled = tpu_compile(fn, *shapes)
    assert "tpu_custom_call" in compiled.as_text(), name


@pytest.fixture(scope="module")
def whisper_block(tpu_compile):
    """The lm phase's fused block — R rounds of C clients × K local
    steps of whisper-tiny at its published widths, Δ-SGD kernel pair
    compiled in — compiled once for both tests below. Returns
    (compiled, C, padded N)."""
    from repro.core import (flatten_fl_state, get_client_opt,
                            get_server_opt, init_fl_state, make_fl_loop,
                            make_loss)
    a = _lm_args()
    cfg, model, params = _whisper()
    copt, sopt = get_client_opt("delta_sgd"), get_server_opt("fedavg")
    loop = make_fl_loop(make_loss(lambda p, b: model.loss(p, b)), copt,
                        sopt, params_like=params, num_rounds=a.rounds,
                        rounds_per_call=a.rounds_per_call, flat="pallas")
    fst = jax.eval_shape(
        lambda p: flatten_fl_state(init_fl_state(p, sopt), loop.layout),
        params)
    lead = (a.rounds_per_call, a.clients_per_round, a.local_steps,
            a.batch)
    data = {"tokens": _sds(lead + (a.seq,), jnp.int32),
            "labels": _sds(lead + (a.seq,), jnp.int32),
            "frames": _sds(lead + (cfg.encoder_seq, cfg.d_model))}
    compiled = tpu_compile(loop, fst, data, donate_argnums=0)
    return compiled, a.clients_per_round, loop.layout.padded_size


def test_whisper_fused_block_fits_v5e(whisper_block):
    """The fused block is one program that fits the chip's HBM."""
    compiled, _, _ = whisper_block
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    print(mem)
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total < V5E_HBM, total


_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\w+)\[([0-9,]*)\]\S* "
                    r"([\w-]+)\(")


def test_whisper_fused_block_keeps_the_slab_layout(whisper_block):
    """The local-step scan carries the client slabs in the Δ-SGD pair's
    own (C, N/128, 128) form: the optimized HLO holds no copy, select
    or fusion in the ``delta_sgd`` scope that writes a whole client
    buffer (a relayout between a (C, N) form and the pair's, or a
    valid-lane select beside the pair), and the pair's two custom calls
    take the slab itself. Interpret mode cannot see either: only the
    TPU compiler's layouts make a reshape a copy."""
    compiled, C, N = whisper_block
    txt = compiled.as_text()
    slab = f"f32[{C},{N // 128},128]"
    passes, pair = [], []
    for ln in txt.splitlines():
        scope = re.search(r'op_name="([^"]*)"', ln)
        if not scope or "/delta_sgd/" not in scope.group(1):
            continue
        if 'custom_call_target="tpu_custom_call"' in ln:
            pair.append(ln)
            continue
        m = _INSTR.match(ln)
        if not m:
            continue
        name, _, dims, op = m.groups()
        elems = 1
        for d in filter(None, dims.split(",")):
            elems *= int(d)
        if elems == C * N and op in ("copy", "select", "fusion"):
            passes.append(name)
    assert not passes, passes
    assert len(pair) == 2, [ln.strip()[:120] for ln in pair]
    for ln in pair:
        operands = ln.split("operand_layout_constraints=", 1)[1]
        operands = operands.split("frontend_attributes", 1)[0]
        assert operands.count(slab) == 2, (slab, operands)
