"""Plain reference of federated rounds with Δ-SGD clients and a FedAvg
server (Kim et al., ICLR 2024, Algorithm 1 and Eq. 4), and the numbers
that compare a program's rounds with it.

Per round every client starts from the global parameters x, resets
η = η₀, θ = θ₀, and takes K local steps on its own batches:

    η_k = η₀                                           (k = 0)
    η_k = min(γ‖x_k − x_{k−1}‖ / (2‖g_k − g_{k−1}‖),
              sqrt(1 + δθ_{k−1}) η_{k−1})              (k > 0)
    θ_k = η_k / η_{k−1},   x_{k+1} = x_k − η_k g_k

with ‖x_k − x_{k−1}‖ = η_{k−1}‖g_{k−1}‖ and norms over all parameters.
The server takes the mean of the clients' final parameters. Written
from the paper; it imports nothing of the program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


@jax.jit
def change_norms(new, old):
    """Per-leaf norm of new − old, in float32."""
    return leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        new, old))


def run_rounds(loss_fn, params, rounds, hyper, step_counts=None):
    """``rounds``: list of client batch dicts with leaves (C, K, ...).
    ``loss_fn(params, batch) -> scalar``, already jitted or jittable;
    it fixes the precision. ``hyper``: ``gamma``, ``delta``, ``eta0``
    and ``theta0``. ``step_counts``, where given, holds per round the
    (C,) local step budgets: client c takes only its first K_c steps,
    and the round's loss is the mean over the steps that ran.

    Returns (final params, per-round list of {loss, eta_mean}, per-leaf
    norms of the first round's change)."""
    gamma, delta = hyper["gamma"], hyper["delta"]
    eta0, theta0 = hyper["eta0"], hyper["theta0"]
    vg = jax.jit(jax.value_and_grad(loss_fn))
    step = jax.jit(lambda x, g, eta: jax.tree.map(
        lambda a, b: a - jnp.asarray(eta, a.dtype) * b, x, g))
    dnorm = jax.jit(lambda a, b: global_norm(jax.tree.map(
        lambda u, v: u.astype(jnp.float32) - v.astype(jnp.float32), a, b)))
    gnorm = jax.jit(global_norm)
    add = jax.jit(lambda s, x: jax.tree.map(
        lambda a, b: a + b.astype(a.dtype), s, x))
    scale = jax.jit(lambda s, n, like: jax.tree.map(
        lambda a, b: (a / n).astype(b.dtype), s, like))
    rows, first = [], None
    P = params
    for r, batches in enumerate(rounds):
        C = jax.tree.leaves(batches)[0].shape[0]
        K = jax.tree.leaves(batches)[0].shape[1]
        acc, losses, etas = None, [], []
        for c in range(C):
            x, gprev, eta, theta, pgn = P, None, eta0, theta0, 0.0
            steps = K if step_counts is None else int(step_counts[r][c])
            for k in range(steps):
                b = jax.tree.map(lambda a: a[c, k], batches)
                l, g = vg(x, b)
                losses.append(float(l))
                if k == 0:
                    eta_k, theta_k = eta0, theta
                else:
                    dx = eta * pgn
                    dg = float(dnorm(g, gprev))
                    cand1 = gamma * dx / (2.0 * dg) if dg > 0 else math.inf
                    cand2 = math.sqrt(1.0 + delta * theta) * eta
                    eta_k = min(cand1, cand2)
                    theta_k = eta_k / eta
                x = step(x, g, eta_k)
                gprev, pgn = g, float(gnorm(g))
                eta, theta = eta_k, theta_k
            etas.append(eta)
            f32 = jax.tree.map(lambda a: a.astype(jnp.float32), x)
            acc = f32 if acc is None else add(acc, f32)
        newP = scale(acc, float(C), P)
        if first is None:
            first = np.asarray(change_norms(newP, P))
        P = newP
        rows.append({"loss": float(np.mean(losses)),
                     "eta_mean": float(np.mean(etas))})
    return P, rows, first


def compare(prog_rows, prog_change, ref_rows, ref_change, ref_first):
    """The numbers a training cell holds to its limits.

    * ``loss``: the largest relative gap of a round's mean loss;
    * ``eta``: the largest relative gap of a round's mean Δ-SGD step
      size at the round's end (it is made from gradient norms);
    * ``change``: over leaves, the largest gap between the program's
      and the reference's norm of the parameters' change over the
      compared rounds, against the larger of that leaf's reference norm
      and the median leaf's. Leaves whose first-round change in the
      reference is under a thousandth of the median leaf's move by
      round-off alone and are left out.
    """
    loss = max(abs(p["loss"] - r["loss"]) / abs(r["loss"])
               for p, r in zip(prog_rows, ref_rows))
    eta = max(abs(p["eta_mean"] - r["eta_mean"]) / abs(r["eta_mean"])
              for p, r in zip(prog_rows, ref_rows))
    keep = ref_first >= 1e-3 * np.median(ref_first)
    med = float(np.median(ref_change[keep]))
    gaps = (np.abs(prog_change - ref_change)
            / np.maximum(ref_change, med))[keep]
    return {"loss": float(loss), "eta": float(eta),
            "change": float(np.max(gaps)),
            "leaves_compared": int(keep.sum()),
            "leaves_left_out": int((~keep).sum())}
