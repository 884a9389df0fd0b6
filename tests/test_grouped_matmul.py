"""Grouped matrix product over held experts: the Pallas kernel (interpret
mode here) against the jnp reference, forward and backward, under the
round body's vmap, with empty groups, a trailing group no matrix
serves, and row counts off the kernel's row tile."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.grouped_matmul import ops, ref

CASES = {
    # (rows, k, n, held groups, group sizes with the unserved group last)
    "uneven": (256, 64, 96, 3, (30, 0, 100, 126)),
    "all_unserved": (128, 32, 64, 2, (0, 0, 128)),
    "off_tile": (200, 64, 32, 4, (17, 40, 0, 3, 140)),
    "all_in_one": (256, 32, 32, 2, (256, 0, 0)),
}


def _dense(x, w, sizes):
    """Row by row, in float64: row r of group g < len(w) times w[g]."""
    x, w = np.asarray(x, np.float64), np.asarray(w, np.float64)
    out = np.zeros((x.shape[0], w.shape[2]))
    start = 0
    for g, n in enumerate(sizes):
        if g < w.shape[0]:
            out[start:start + n] = x[start:start + n] @ w[g]
        start += n
    return out


@pytest.mark.parametrize("case", sorted(CASES))
def test_reference_matches_row_by_row(case):
    m, k, n, held, sizes = CASES[case]
    x = jax.random.normal(jax.random.key(0), (m, k))
    w = jax.random.normal(jax.random.key(1), (held, k, n))
    gs = jnp.asarray(sizes, jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = ref.gmm(x, w, gs)
    np.testing.assert_allclose(np.asarray(got), _dense(x, w, sizes),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_reference_forward_and_backward(case):
    """Values and both gradients of a vmapped product (a client axis of
    2, each row with its own group sizes), kernel against reference."""
    m, k, n, held, sizes = CASES[case]
    keys = jax.random.split(jax.random.key(2), 3)
    x = jax.random.normal(keys[0], (2, m, k))
    w = jax.random.normal(keys[1], (2, held, k, n))
    dy = jax.random.normal(keys[2], (2, m, n))
    gs = jnp.asarray([sizes, sizes[::-1][1:] + sizes[-1:]], jnp.int32)
    gs = gs.at[1, -1].set(m - int(jnp.sum(gs[1, :-1])))

    def loss(x, w, pallas):
        y = jax.vmap(lambda a, b, c: ops.grouped_matmul(
            a, b, c, pallas=pallas))(x, w, gs)
        return jnp.sum(y * dy)

    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(loss, argnums=(0, 1))(x, w, True)
        want = jax.value_and_grad(loss, argnums=(0, 1))(x, w, False)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        scale = float(jnp.max(jnp.abs(b))) or 1.0
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b) / scale, atol=1e-5)


def test_rows_no_matrix_serves_are_zero_and_get_no_gradient():
    m, k, n, held, sizes = CASES["uneven"]
    x = jax.random.normal(jax.random.key(3), (m, k))
    w = jax.random.normal(jax.random.key(4), (held, k, n))
    gs = jnp.asarray(sizes, jnp.int32)
    y = ops.grouped_matmul(x, w, gs, pallas=True)
    assert float(jnp.abs(y[-sizes[-1]:]).max()) == 0.0
    gx = jax.grad(lambda a: jnp.sum(
        ops.grouped_matmul(a, w, gs, pallas=True)))(x)
    assert float(jnp.abs(gx[-sizes[-1]:]).max()) == 0.0
    assert float(jnp.abs(gx[:30]).min()) > 0.0


def test_the_platform_decides_the_path(monkeypatch):
    """Off the TPU the reference runs (no Pallas call in the program);
    on it the kernel, compiled."""
    import repro.kernels as rk
    m, k, n, held, sizes = CASES["uneven"]
    args = (jnp.ones((m, k)), jnp.ones((held, k, n)),
            jnp.asarray(sizes, jnp.int32))
    text = jax.jit(ops.grouped_matmul).lower(*args).as_text()
    assert "pallas" not in text.lower()
    monkeypatch.setattr(rk, "on_tpu", lambda: True)
    calls = []
    monkeypatch.setattr(ops, "_grouped", lambda *a: calls.append(a[3:]))
    ops.grouped_matmul(*args)
    assert calls == [(True, False)]
