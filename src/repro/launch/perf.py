import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Perf hillclimbing driver (§Perf): lower a (arch × shape) pair under the
baseline (paper-faithful) configuration and under beyond-paper optimization
variants, and report the calibrated roofline terms side by side.

  PYTHONPATH=src python -m repro.launch.perf --pair zamba2-7b/train_4k \
      --variants baseline,seq_shard

Variants:
  baseline     paper-faithful configuration (reuses the sweep artifact)
  seq_shard    Megatron-SP analog: residual stream sharded over `model`
               along sequence (row-parallel epilogues -> reduce-scatter)
  softmax_bf16 bf16 softmax-weight storage between the attention matmuls
  quant_kv     int8 KV cache entries + f16 scales (decode shapes)
  flat_fed     flat-parameter Δ-SGD engine (train shapes): client params
               packed into one (C, N) buffer for the whole local scan
  flat_fed_sharded
               flat engine with the (C, N) buffer mesh-sharded per
               FederationSpec.flat_spec (clients over client axes, N over
               fsdp/tp axes); the compiled HLO is asserted to contain NO
               rematerialization of the full (C, N) buffer
               (repro.sharding.hlo.assert_flat_buffer_sharded)
  flat_fed_hetero
               sharded flat engine under the `dirichlet_stragglers`
               scenario: per-client step counts K_c ≤ K drawn each round
               and lowered as η=0 lane masks (repro.federation); HLO
               assertion as above
  flat_fed_async
               sharded flat engine under the `zipf_async` scenario:
               FedBuff-style staleness-weighted delta buffer in
               FLState.buffer; HLO assertion as above
  flat_fed_compressed
               sharded flat engine with int8 delta compression + EF21
               error feedback under the `bandwidth_tiered` scenario
               (repro.compression): client deltas are compressed
               chunk-locally BEFORE the client-mean psum. Reports the
               analytic wire-bytes / compression-ratio telemetry and
               runs BOTH HLO assertions (sharded buffer + no
               full-precision delta across the client boundary, the
               latter skipped with a note when the production spec
               leaves < 2 clients per client shard)
  flat_fed_rounds_fused
               round-fused training loop (repro.core.fed_loop): 8
               rounds as ONE jitted lax.scan on the sharded flat
               engine, donated carry; sharded-buffer HLO assertion on
               the scanned computation
  flat_fed_faults
               chaos round (repro.federation.faults): deterministic
               dropouts + NaN corruption + byzantine scaling under
               trimmed robust aggregation with quorum, on int8+EF
               compression; both HLO assertions as flat_fed_compressed
"""
import argparse
import json
import time

import jax.numpy as jnp

from repro import roofline
from repro.compression import CompressionSpec
from repro.configs import FLConfig, INPUT_SHAPES, get_config
from repro.federation import get_scenario
from repro.launch.dryrun import _at_depth, _calib_depths, _compile_step
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import federation_kind
from repro.sharding.spec import get_federation_spec

VARIANT_KNOBS = {
    "baseline": {},
    "seq_shard": {"seq_shard": True},
    "softmax_bf16": {"softmax_bf16": True},
    "seq_shard+softmax_bf16": {"seq_shard": True, "softmax_bf16": True},
    "quant_kv": {"quant_kv": True},
    "cache_seq_shard": {"cache_seq_shard": True},
    "quant_kv+cache_seq_shard": {"quant_kv": True, "cache_seq_shard": True},
    # flat-parameter Δ-SGD engine: packed (C, N) client-state buffer,
    # 2 fused update ops per local step instead of per-leaf/per-client
    "flat_fed": {"flat_fed": True},
    # mesh-native flat engine: the (C, N) buffer stays sharded per
    # FederationSpec.flat_spec end to end (shard_map kernel pair + psum
    # dual-norm reduction); compiled HLO is checked for remat copies
    "flat_fed_sharded": {"flat_fed": True, "flat_sharded": True},
    # federation scenarios (repro.federation) on the sharded flat engine:
    # heterogeneous per-client step counts lowered as η=0 lane masks
    # (dirichlet_stragglers), and FedBuff-style async buffered
    # aggregation with staleness-weighted merges (zipf_async). Both keep
    # the 2-launch/step invariant and the sharded-buffer HLO assertion.
    "flat_fed_hetero": {"flat_fed": True, "flat_sharded": True,
                        "scenario": "dirichlet_stragglers"},
    "flat_fed_async": {"flat_fed": True, "flat_sharded": True,
                       "scenario": "zipf_async"},
    # delta compression (repro.compression): int8 + EF21 client deltas
    # under the bandwidth_tiered scenario, compressed shard-locally
    # before the client-mean psum; wire-bytes/compression-ratio
    # telemetry lands in the perf artifact next to the roofline terms.
    # error_feedback=True matters: it allocates FLState.ef, so the
    # compiled program (and both HLO assertions) covers the EF sharding
    "flat_fed_compressed": {"flat_fed": True, "flat_sharded": True,
                            "scenario": "bandwidth_tiered",
                            "compression": CompressionSpec(
                                kind="int8", error_feedback=True)},
    # round-fused training loop (repro.core.fed_loop): 8 rounds as one
    # lax.scan on the sharded flat engine — proves the fused program
    # lowers/compiles on the production mesh and that the sharded-buffer
    # HLO assertion holds on the SCANNED computation (cost-analysis
    # counts the round body once, so the roofline terms are per-round)
    "flat_fed_rounds_fused": {"flat_fed": True, "flat_sharded": True,
                              "rounds_per_call": 8},
    # chaos round (repro.federation.faults): mid-round dropouts + NaN
    # corruption + byzantine scaling defended by trimmed robust
    # aggregation under quorum Q=2, stacked on int8+EF compression —
    # proves the guarded round tail lowers on the production mesh with
    # both HLO assertions (as in flat_fed_compressed)
    "flat_fed_faults": {"flat_fed": True, "flat_sharded": True,
                        "scenario": get_scenario(
                            "dirichlet_dropouts", robust_agg="trimmed",
                            byzantine_rate=0.1),
                        "compression": CompressionSpec(
                            kind="int8", error_feedback=True)},
}


def _check_flat_sharded(compiled, cfg, mesh, spec, variant,
                        compressed=False):
    """flat_fed_sharded copy-count assertion: the compiled module must
    never rematerialize the full packed (C, N) buffer on one device.
    ``compressed`` additionally asserts no full-precision client delta
    crosses the client shard boundary (skipped with a note when the
    spec leaves < 2 clients per client shard — indistinguishable from
    the aggregated mean)."""
    import jax
    import jax.numpy as jnp

    from repro.core import flat as flatlib
    from repro.models.model import build_model
    from repro.sharding.hlo import (assert_flat_buffer_sharded,
                                    assert_no_fullprec_delta_collective)

    model = build_model(cfg, jnp.bfloat16)
    pstruct = jax.eval_shape(model.init, jax.random.key(0))
    layout = flatlib.layout_of(pstruct, shards=spec.flat_shards(mesh))
    C = spec.clients_on(mesh)
    rep = assert_flat_buffer_sharded(compiled, C, layout.padded_size)
    print(f"[{variant}] ({C}, {layout.padded_size}) flat buffer stays "
          f"sharded: 0 full-shape HLO hits "
          f"(gather/copy={rep['gather_or_copy']})", flush=True)
    if compressed:
        try:
            brep = assert_no_fullprec_delta_collective(
                compiled, C, layout.padded_size, mesh=mesh,
                federation=spec)
            print(f"[{variant}] no full-precision delta crosses the "
                  f"client boundary ({brep['collectives']} collectives "
                  f"checked)", flush=True)
        except ValueError as e:
            print(f"[{variant}] boundary check skipped: {e}", flush=True)


def measure(arch: str, shape_id: str, variant: str, *, local_steps=2):
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_id]
    mesh = make_production_mesh(multi_pod=False)
    spec = get_federation_spec(federation_kind(cfg), mesh)
    fl = FLConfig(local_steps=local_steps)
    knobs = dict(VARIANT_KNOBS[variant])

    t0 = time.time()
    if shape.kind == "train" or True:
        # two-depth calibrated roofline (same methodology as the sweep)
        L1, L2 = _calib_depths(cfg)
        rls = []
        for L in (L1, L2):
            cfg_L = _at_depth(cfg, L)
            c, *_ = _compile_step(cfg_L, shape, mesh, spec, fl,
                                  unroll=True, remat=False, **knobs)
            if knobs.get("flat_sharded"):
                _check_flat_sharded(c, cfg_L, mesh, spec, variant,
                                    compressed=bool(
                                        knobs.get("compression")))
            rls.append(roofline.analyze(c, mesh.size))
        rl = roofline.extrapolate(rls[0], rls[1], L1, L2, cfg.num_layers)
    out = rl.summary()
    out["wall_s"] = round(time.time() - t0, 1)
    if knobs.get("compression"):
        # analytic wire telemetry: per-round client->server payload for
        # the FULL-depth config at this variant's compression kind
        # (bandwidth-tiered rounds mix levels per draw; this is the
        # fixed-kind figure the ratio columns are normalized against)
        import jax
        import jax.numpy as jnp
        from repro.compression import get_compression
        from repro.core import flat as flatlib
        from repro.models.model import build_model
        comp = get_compression(knobs["compression"])
        pstruct = jax.eval_shape(build_model(cfg, jnp.bfloat16).init,
                                 jax.random.key(0))
        layout = flatlib.layout_of(pstruct, shards=spec.flat_shards(mesh))
        C = spec.clients_on(mesh)
        table = comp.level_wire_bytes(layout.size)
        wire = float(table[comp.level]) * C
        out["wire"] = {"kind": comp.kind, "clients": C,
                       "wire_bytes_round": wire,
                       "uncompressed_bytes_round": float(table[0]) * C,
                       "comp_ratio": float(table[0]) / float(
                           table[comp.level])}
        print(f"[{variant}] wire: {wire/1e9:.2f} GB/round vs "
              f"{float(table[0]) * C/1e9:.2f} GB uncompressed "
              f"(ratio {out['wire']['comp_ratio']:.2f}x)", flush=True)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pair", required=True)  # arch/shape
    ap.add_argument("--variants", default="baseline,seq_shard")
    ap.add_argument("--out", default="experiments/perf")
    args = ap.parse_args()
    arch, shape_id = args.pair.split("/")
    os.makedirs(args.out, exist_ok=True)
    results = {}
    base_path = os.path.join("experiments/dryrun",
                             f"{arch}_{shape_id}_single.json")
    for v in args.variants.split(","):
        if v == "baseline" and os.path.exists(base_path):
            with open(base_path) as f:
                results[v] = json.load(f)["roofline"]
            print(f"[{v}] reused sweep artifact")
        else:
            print(f"[{v}] lowering...", flush=True)
            results[v] = measure(arch, shape_id, v)
        r = results[v]
        print(f"  t_comp={r['t_compute_s']:.3e} t_mem={r['t_memory_s']:.3e} "
              f"t_coll={r['t_collective_s']:.3e} bot={r['bottleneck']}",
              flush=True)
    tag = f"{arch}_{shape_id}".replace("/", "_")
    with open(os.path.join(args.out, tag + ".json"), "w") as f:
        json.dump(results, f, indent=2, default=float)


if __name__ == "__main__":
    main()
