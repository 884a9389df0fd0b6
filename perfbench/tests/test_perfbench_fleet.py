"""The fleet cell (its entries held back, ``fleet_pending``) at a size
the CPU holds (1 000 registered clients,
cohort 8, K_max 3, batch 8): a sound run is correct; a run with the
timed path broken underneath is not, on the number each fault should
break; the controls (the reference put in the program's place, in
bfloat16 or with a fault) fail a limit; the reference's draws and
partitions equal the program's; the fleet loop's compiled ops carry the
round body's layer scopes that the cell's per-layer readers read; and
full step budgets leave the reference's rounds bit for bit as they
were."""
import os
import sys
import types

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import run as bench_run  # noqa: E402
from harness import fleetref, scopes  # noqa: E402
from fleet_pending import fleet_cell  # noqa: E402

SEED = 2 ** 31 + 7


def tiny_cell():
    cell = fleet_cell()
    mix = cell.mix
    cell.mix = dict(mix, registered=1000, participation=0.008, batch=8,
                    samples_per_partition=24, partitions=10,
                    start_round_max=1000,
                    task=dict(mix["task"], n_train=2000))
    return cell


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    """One persistent compile cache for the module's runs; the process's
    cache settings are put back afterwards."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    path = tmp_path_factory.mktemp("fleet")
    old = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(path / "cache")
    yield path
    if old is None:
        os.environ.pop("JAX_COMPILATION_CACHE_DIR")
    else:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = old
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def measure(tmp, monkeypatch):
    import jax
    from drivers import fleet
    cell = tiny_cell()
    orig = fleet.Build.__init__

    def build(self, cell, ckpt_root=str(tmp)):
        orig(self, cell, ckpt_root)
    monkeypatch.setattr(fleet.Build, "__init__", build)
    args = types.SimpleNamespace(seed=SEED, seconds=0.3, trace=0)
    line = bench_run.measure(cell, args, jax.devices()[:1],
                             trace_root=str(tmp))
    return line["correct"], {c["name"]: c["value"]
                             for c in line["compared"]}


def test_fleet_sound(cache_dir, monkeypatch):
    ok, nums = measure(cache_dir, monkeypatch)
    assert ok, nums
    assert nums["cohort"] == 0 and nums["arena"] == 0
    assert nums["compiles_in_window"] == 0


def _shift_cohort(monkeypatch):
    import jax
    from repro.federation import schedulers
    orig = schedulers.Scheduler.sample

    def sample(self, key, round_idx):
        return orig(self, key, jax.numpy.asarray(round_idx) + 1)
    monkeypatch.setattr(schedulers.Scheduler, "sample", sample)


def _ignore_budgets(monkeypatch):
    import jax.numpy as jnp
    from repro.federation import heterogeneity

    def draw(self, key, num_clients, k_max):
        return jnp.full((num_clients,), k_max, jnp.int32)
    monkeypatch.setattr(heterogeneity.SpeedModel, "draw", draw)


def _skip_scatter(monkeypatch):
    from repro.federation import arena
    monkeypatch.setattr(arena, "arena_update", lambda a, ids, rows: a)


def _state_unchanged(monkeypatch):
    import repro.core as core
    orig = core.make_fleet_loop

    def broken(*a, **k):
        loop = orig(*a, **k)

        def frozen(carry, data, client_weights=None, arena=None):
            _, mets = loop(carry, data, client_weights, arena)
            return carry, mets
        frozen.__dict__.update(loop.__dict__)
        return frozen
    monkeypatch.setattr(core, "make_fleet_loop", broken)


def _half_batch(monkeypatch):
    import jax
    import jax.numpy as jnp
    from repro.models import small

    def half_ce(logits, y):
        lg = logits.astype(jnp.float32)
        ll = jnp.take_along_axis(lg, y[:, None], axis=-1)[:, 0]
        lse = jax.nn.logsumexp(lg, axis=-1)
        return jnp.mean((lse - ll)[: y.shape[0] // 2])
    monkeypatch.setattr(small, "softmax_ce", half_ce)


@pytest.mark.parametrize("fault,breaks", [
    (_shift_cohort, ("cohort",)),
    (_ignore_budgets, ("loss", "eta", "change")),
    (_skip_scatter, ("arena",)),
    (_state_unchanged, ("change",)),
    (_half_batch, ("loss", "eta", "change")),
], ids=["cohort_shifted", "budgets_ignored", "scatter_skipped",
        "state_unchanged", "half_batch"])
def test_fleet_fault_is_not_correct(cache_dir, monkeypatch, fault, breaks):
    fault(monkeypatch)
    ok, nums = measure(cache_dir, monkeypatch)
    limits = fleet_cell().limits
    assert not ok, nums
    assert any(nums[k] > limits[k] for k in breaks), nums


@pytest.fixture(scope="module")
def first(cache_dir):
    from drivers.fleet import Build
    from harness.common import Spans
    b = Build(tiny_cell(), ckpt_root=str(cache_dir))
    prog = b.first_block(SEED, Spans())
    ref = b.reference(SEED, prog["t0"])
    return b, prog, ref


@pytest.mark.parametrize("control,breaks", [
    ({"dtype": "bfloat16"}, ("loss", "eta", "change")),
    ({"cohort_shift": 1}, ("cohort",)),
    ({"full_budgets": True}, ("loss", "eta", "change")),
    ({"skip_scatter": True}, ("arena",)),
], ids=["bf16", "cohort_shifted", "budgets_ignored", "scatter_skipped"])
def test_fleet_control_fails(first, control, breaks):
    import jax.numpy as jnp
    from drivers.fleet import compare
    b, prog, ref = first
    limits = fleet_cell().limits
    assert not any(v > limits[k] for k, v in compare(prog, ref).items()
                   if k in limits)
    kw = dict(control)
    if "dtype" in kw:
        kw["dtype"] = getattr(jnp, kw["dtype"])
    ctl = b.reference(SEED, prog["t0"], **kw)
    nums = compare(ctl, ref)
    assert [k for k in breaks if nums[k] > limits[k]], nums


def test_reference_draws_equal_the_programs(first):
    """Partitions, and the cohort, step budgets and example ids of four
    rounds: the reference's derivation against the data pipeline, the
    scheduler and the speed model."""
    import jax
    from harness import traffic
    b, prog, _ = first
    fed, sc = prog["fed"], b.scn_mix
    x, y = traffic.gaussian_mixture(SEED, b.mix["task"])
    np.testing.assert_array_equal(x, fed.task.x)
    parts = b.partitions(SEED, y)
    assert len(parts) == len(fed.clients)
    for mine, theirs in zip(parts, fed.clients):
        np.testing.assert_array_equal(mine, theirs)
    sch = b.scn.make_scheduler(b.M, b.C)
    for t in (prog["t0"], prog["t0"] + 1, 5, 999):
        ids = fleetref.cohort(sc["seed"], t, b.M, b.C, sc["zipf_s"])
        np.testing.assert_array_equal(
            ids, np.asarray(sch.sample(jax.random.key(sc["seed"]), t)))
        np.testing.assert_array_equal(
            fleetref.step_budgets(sc["seed"], t, b.C, b.K,
                                  sc["k_min_frac"]),
            np.asarray(b.scn.draw_step_counts(t, b.C, b.K)))
        take, _, got = fed.sample_round_indices(
            b.fl.participation, b.K, b.b, round_idx=t)
        np.testing.assert_array_equal(got, ids)
        np.testing.assert_array_equal(
            take, fleetref.example_ids(fed.seed, t, ids, parts, b.K, b.b))
    # and the program's first block drew the reference's cohorts
    for row, t in zip(prog["rows"], range(prog["t0"], prog["t0"] + b.R)):
        np.testing.assert_array_equal(
            row["cohort_ids"],
            fleetref.cohort(sc["seed"], t, b.M, b.C, sc["zipf_s"]))


def test_fleet_loop_ops_carry_the_round_layers(first):
    """The compiled fleet loop keeps the round body's layer scopes in
    its ops' op_name paths, where the cell's device-layer readers find
    them, and its dots are all under ``client_grad``."""
    import re

    import jax
    import jax.numpy as jnp
    from repro.core import flatten_fl_state, init_fl_state
    b, _, _ = first
    state = init_fl_state(b.weights(1), b.sopt, b.scn, cohort=b.C)
    fst = flatten_fl_state(state, b.loop.layout)
    idx = jax.ShapeDtypeStruct((b.R, b.C, b.K, b.b), jnp.int32)
    ex = {"x": jax.ShapeDtypeStruct((100, 32), jnp.float32),
          "y": jax.ShapeDtypeStruct((100,), jnp.int32)}
    text = jax.jit(b.loop).lower((fst, b.arena0()), idx,
                                 arena=ex).compile().as_text()
    layers, dots = set(), []
    for line in text.splitlines():
        m = re.search(r'op_name="([^"]*)"', line)
        if not m:
            continue
        layers.add(scopes.layer_of(m.group(1)))
        if re.search(r"\bdot\(", line):
            dots.append(scopes.layer_of(m.group(1)))
    assert set(scopes.LAYERS) <= layers, layers
    assert dots and set(dots) == {"client_grad"}, dots


@pytest.mark.parametrize("n_train,parts,per,alpha", [
    (50000, 100, 500, 0.1),
    (2000, 10, 300, 0.01),
    (5000, 20, 100, 0.5),
    (3000, 7, 250, 1.0),
], ids=["cell", "classes_run_out", "alpha_half", "alpha_one"])
def test_partitions_equal_the_programs(n_train, parts, per, alpha):
    """The reference's latent-Dirichlet partitions against the program's
    partitioner, on the traffic's examples: the cell's own size, and
    sizes where classes run out and the concentration rounds
    differently."""
    from drivers.fleet import data_seed
    from harness import traffic
    from repro.data.dirichlet import dirichlet_partition
    task = dict(fleet_cell().mix["task"], n_train=n_train)
    _, y = traffic.gaussian_mixture(SEED + parts, task)
    d = data_seed(SEED + parts)
    mine = fleetref.partitions(y, parts, alpha, per, d)
    theirs = dirichlet_partition(y, parts, alpha, per, seed=d)
    assert [len(p) for p in mine] == [per] * parts
    for a, b in zip(mine, theirs):
        np.testing.assert_array_equal(a, b)


def test_full_budgets_give_the_plain_rounds(first):
    """``fedref.run_rounds`` with every budget at K runs the rounds the
    lm cell's reference runs without budgets, bit for bit."""
    import jax
    from harness import fedref
    b, prog, _ = first
    x, y = prog["fed"].task.x, prog["fed"].task.y
    take = np.arange(b.C * b.K * b.b).reshape(b.C, b.K, b.b)
    rounds = [{"x": x[take + r], "y": y[take + r]} for r in range(2)]
    params = b.weights(3)
    with jax.default_matmul_precision("highest"):
        plain = fedref.run_rounds(b.ref.loss, params, rounds, b.hyper)
        full = fedref.run_rounds(b.ref.loss, params, rounds, b.hyper,
                                 step_counts=[[b.K] * b.C] * 2)
    assert plain[1] == full[1]
    for p, f in zip(jax.tree.leaves(plain[0]), jax.tree.leaves(full[0])):
        np.testing.assert_array_equal(np.asarray(p), np.asarray(f))
    np.testing.assert_array_equal(plain[2], full[2])
