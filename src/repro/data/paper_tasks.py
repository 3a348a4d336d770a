"""The paper's experiment tasks: linear / logistic / lasso regression and a
1-hidden-layer neural network, distributed over M workers.

Dataset notes (offline container): the real datasets used in the paper
(ijcnn1, MNIST, Housing, Body fat, Abalone, Ionosphere, Adult, Derm) are not
downloadable here, so each benchmark uses a synthetic stand-in with matched
(n_samples, n_features, n_workers) and controlled smoothness constants. The
paper's *relative* claims (communication ratios, iteration parity with HB)
are what we validate; see DESIGN.md §7.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.simulator import FedTask


# ---------------------------------------------------------------- helpers
def _split_workers(x: np.ndarray, y: np.ndarray, m: int):
    n = (x.shape[0] // m) * m
    xs = x[:n].reshape(m, n // m, x.shape[1])
    ys = y[:n].reshape(m, n // m)
    return xs, ys


def _rescale_to_smoothness(x: np.ndarray, target_hess_lmax: float) -> np.ndarray:
    """Scale X so that lambda_max(X^T X) == target_hess_lmax."""
    lmax = float(np.linalg.eigvalsh(x.T @ x)[-1])
    return x * np.sqrt(target_hess_lmax / lmax)


def _features(rng, n: int, d: int, condition: float) -> np.ndarray:
    """Gaussian features with a geometric per-column scale.

    condition > 1 makes the Hessian ill-conditioned (kappa ~ condition^2),
    which matches the iteration counts of the paper's real datasets
    (hundreds to thousands) — the regime where censoring actually fires.
    Well-conditioned random Gaussians converge in ~20 iterations and no
    algorithm ever censors (see EXPERIMENTS.md §Repro notes).
    """
    x = rng.standard_normal((n, d))
    if condition > 1.0:
        scale = condition ** (-np.arange(d) / max(d - 1, 1))
        x = x * scale[None, :]
    return x


@dataclasses.dataclass
class TaskBundle:
    task: FedTask
    L: float                 # global smoothness constant of f = sum_m f_m
    L_m: np.ndarray          # (M,) per-worker smoothness constants
    alpha_paper: float       # the step size the paper uses for this setup


# ------------------------------------------------------- linear regression
def make_linear_regression(m: int = 9, n_per: int = 50, d: int = 50,
                           worker_L: Sequence[float] | None = None,
                           seed: int = 0,
                           condition: float = 1.0) -> TaskBundle:
    """f_m(theta) = 0.5 ||X_m theta - y_m||^2.

    Default worker smoothness follows the paper's Fig. 1/2 setting
    L_m = (1.3^(m-1))^2, m = 1..9.
    """
    rng = np.random.default_rng(seed)
    if worker_L is None:
        worker_L = [(1.3 ** i) ** 2 for i in range(m)]
    xs, ys = [], []
    for i in range(m):
        y = rng.choice([-1.0, 1.0], size=n_per)
        x = _features(rng, n_per, d, condition)
        x = _rescale_to_smoothness(x, worker_L[i])
        xs.append(x)
        ys.append(y)
    X = np.stack(xs)    # (M, n, d)
    Y = np.stack(ys)    # (M, n)
    H = sum(x.T @ x for x in xs)
    L = float(np.linalg.eigvalsh(H)[-1])

    def loss_fn(theta, data):
        x, y = data
        r = x @ theta - y
        return 0.5 * jnp.sum(r * r)

    def grad_fn(theta, data):
        x, y = data
        return x.T @ (x @ theta - y)

    task = FedTask(init_params=jnp.zeros((d,)),
                   grad_fn=grad_fn, loss_fn=loss_fn,
                   worker_data=(jnp.asarray(X), jnp.asarray(Y)),
                   name="linear_regression")
    return TaskBundle(task=task, L=L, L_m=np.asarray(worker_L),
                      alpha_paper=1.0 / L)


# ----------------------------------------------------- logistic regression
def make_logistic_regression(m: int = 9, n_per: int = 50, d: int = 50,
                             worker_L: Sequence[float] | None = None,
                             reg: float = 0.001, seed: int = 1,
                             condition: float = 25.0) -> TaskBundle:
    """f_m = sum_n log(1+exp(-y x.theta)) + (reg/(2M))||theta||^2.

    Default: the paper's Fig. 3 setting with common L_1=..=L_9=4.
    Worker smoothness of the logistic term is lmax(X^T X)/4.
    """
    rng = np.random.default_rng(seed)
    if worker_L is None:
        worker_L = [4.0] * m
    xs, ys = [], []
    for i in range(m):
        y = rng.choice([-1.0, 1.0], size=n_per)
        x = _features(rng, n_per, d, condition)
        # logistic Hessian bound: X^T X / 4 (+ reg/M); rescale the data term
        x = _rescale_to_smoothness(x, 4.0 * (worker_L[i] - reg / m))
        xs.append(x)
        ys.append(y)
    X, Y = np.stack(xs), np.stack(ys)
    H = sum(x.T @ x for x in xs) / 4.0
    L = float(np.linalg.eigvalsh(H)[-1]) + reg

    def loss_fn(theta, data):
        x, y = data
        z = -y * (x @ theta)
        return jnp.sum(jnp.logaddexp(0.0, z)) + \
            reg / (2.0 * m) * jnp.sum(theta * theta)

    grad_fn = jax.grad(loss_fn)
    task = FedTask(init_params=jnp.zeros((d,)),
                   grad_fn=grad_fn, loss_fn=loss_fn,
                   worker_data=(jnp.asarray(X), jnp.asarray(Y)),
                   name="logistic_regression")
    return TaskBundle(task=task, L=L, L_m=np.asarray(worker_L),
                      alpha_paper=1.0 / L)


# ----------------------------------------------------------- lasso (subgrad)
def make_lasso(m: int = 9, n_per: int = 50, d: int = 50,
               reg: float = 0.5, seed: int = 2,
               worker_L: Sequence[float] | None = None,
               condition: float = 6.0) -> TaskBundle:
    """f_m = 0.5||X_m theta - y||^2 + (reg/M)||theta||_1, subgradient used."""
    rng = np.random.default_rng(seed)
    if worker_L is None:
        worker_L = [(1.2 ** i) ** 2 for i in range(m)]
    xs, ys = [], []
    for i in range(m):
        y = rng.choice([-1.0, 1.0], size=n_per)
        x = _rescale_to_smoothness(_features(rng, n_per, d, condition),
                                   worker_L[i])
        xs.append(x)
        ys.append(y)
    X, Y = np.stack(xs), np.stack(ys)
    H = sum(x.T @ x for x in xs)
    L = float(np.linalg.eigvalsh(H)[-1])

    def loss_fn(theta, data):
        x, y = data
        r = x @ theta - y
        return 0.5 * jnp.sum(r * r) + reg / m * jnp.sum(jnp.abs(theta))

    def grad_fn(theta, data):  # subgradient
        x, y = data
        return x.T @ (x @ theta - y) + reg / m * jnp.sign(theta)

    task = FedTask(init_params=jnp.zeros((d,)),
                   grad_fn=grad_fn, loss_fn=loss_fn,
                   worker_data=(jnp.asarray(X), jnp.asarray(Y)),
                   name="lasso")
    return TaskBundle(task=task, L=L, L_m=np.asarray(worker_L),
                      alpha_paper=1.0 / L)


# ------------------------------------------------- 1-hidden-layer NN (paper)
def make_neural_network(m: int = 9, n_per: int = 200, d: int = 22,
                        hidden: int = 30, reg: float | None = None,
                        seed: int = 3) -> TaskBundle:
    """The paper's nonconvex task: one hidden layer, 30 nodes, sigmoid.

    Binary labels; sigmoid output with squared loss + L2 regularization.
    Progress metric is ||grad_k||^2 (StepInfo.agg_grad_sqnorm).

    The data comes from numpy's generator; the initial weights come from
    ``jax.random.normal``, so they depend on JAX's threefry mode
    (``jax_threefry_partitionable``): the same seed draws another init in
    each mode.
    """
    rng = np.random.default_rng(seed)
    n_total = m * n_per
    if reg is None:
        reg = 1.0 / n_total
    w_true = rng.standard_normal((d,))
    X = rng.standard_normal((n_total, d))
    Y = (np.tanh(X @ w_true) + 0.1 * rng.standard_normal(n_total) > 0)
    Y = Y.astype(np.float64)
    Xs, Ys = _split_workers(X, Y, m)

    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    params = {
        "w1": jax.random.normal(k1, (d, hidden)) * (1.0 / np.sqrt(d)),
        "b1": jnp.zeros((hidden,)),
        "w2": jax.random.normal(k2, (hidden, 1)) * (1.0 / np.sqrt(hidden)),
        "b2": jnp.zeros((1,)),
    }

    def loss_fn(p, data):
        x, y = data
        h = jax.nn.sigmoid(x @ p["w1"] + p["b1"])
        out = jax.nn.sigmoid(h @ p["w2"] + p["b2"])[:, 0]
        l2 = sum(jnp.sum(v * v) for v in jax.tree_util.tree_leaves(p))
        return jnp.sum((out - y) ** 2) + reg / (2.0 * m) * l2

    grad_fn = jax.grad(loss_fn)
    task = FedTask(init_params=params, grad_fn=grad_fn, loss_fn=loss_fn,
                   worker_data=(jnp.asarray(Xs), jnp.asarray(Ys)),
                   name="neural_network")
    # nonconvex: no meaningful global L; report a proxy via data scale
    return TaskBundle(task=task, L=float("nan"),
                      L_m=np.full((m,), np.nan), alpha_paper=0.02)


# ------------------------------------------- dataset-shaped synthetic stand-ins
STAND_INS = {
    # name: (n_samples, n_features, paper_workers)
    "ijcnn1": (49990, 22, 9),
    "mnist": (60000, 196, 9),     # 196 = 14x14 downsample scale; keeps eigh cheap
    "housing": (506, 13, 3),
    "bodyfat": (252, 14, 3),
    "abalone": (4177, 8, 3),
    "ionosphere": (351, 33, 3),
    "adult": (1605, 14, 3),
    "derm": (366, 34, 3),
}


def make_standin(name: str, kind: str, seed: int = 7, **kw) -> TaskBundle:
    """Synthetic stand-in with a real dataset's (n, d, M) signature."""
    n, d, m = STAND_INS[name]
    n_per = n // m
    mk = {"linear": make_linear_regression,
          "logistic": make_logistic_regression,
          "lasso": make_lasso,
          "nn": make_neural_network}[kind]
    if kind == "nn":
        return mk(m=m, n_per=min(n_per, 400), d=d, seed=seed, **kw)
    # ill-conditioning matched to the paper's iteration counts (real tabular
    # data): linear ~2e2 iters, logistic ~5e3 iters; worker smoothness spread
    # like the paper's evenly-split real datasets
    condition = {"linear": 8.0, "logistic": 30.0, "lasso": 8.0}[kind]
    bundle = mk(m=m, n_per=min(n_per, 800), d=d, seed=seed,
                condition=condition,
                worker_L=[4.0 * (1.25 ** i) for i in range(m)],
                **kw)
    return dataclasses.replace(bundle, task=bundle.task._replace(
        name=f"{name}_{kind}"))
