"""Federated M-worker simulator running exact Algorithm 1 semantics.

This is the harness behind every paper-reproduction experiment: it owns no
model-specific logic, only (a) per-worker gradient evaluation via vmap and
(b) the CHB-family server update. Everything is jitted with a lax.scan over
iterations, so thousands of iterations of the paper's small problems run in
milliseconds on CPU.

``trajectory`` is the pure scan (no jit), reused by ``repro.sweep`` to run
whole configuration grids as one compiled program; ``run`` is the one-point
convenience wrapper that jits it. For grids of more than a couple of points,
prefer ``repro.sweep.run_sweep`` — it compiles once for the entire grid
instead of once per point.
"""
from __future__ import annotations

from typing import TYPE_CHECKING, Any, Callable, NamedTuple, Union

import jax
import jax.numpy as jnp

if TYPE_CHECKING:   # annotations only; runtime opt imports are lazy so the
    # core <-> opt import graph stays acyclic
    from ..opt.api import FedOptimizer, OptState
    from .chb import FedOptConfig

    OptLike = Union["FedOptimizer", "FedOptConfig"]
else:
    OptLike = Any


class FedTask(NamedTuple):
    """A distributed optimization problem f(theta) = sum_m f_m(theta).

    worker_data leaves are stacked with leading axis M; grad_fn/loss_fn
    operate on ONE worker's slice. The simulator vmaps them.
    """
    init_params: Any
    grad_fn: Callable[[Any, Any], Any]   # (params, data_m) -> grad/subgrad
    loss_fn: Callable[[Any, Any], jax.Array]  # (params, data_m) -> f_m
    worker_data: Any
    name: str = "task"


class History(NamedTuple):
    """Per-iteration trajectory of one Algorithm-1 run.

    Attributes:
      objective: (K,) f(theta^k) recorded *before* iteration k's update.
      comm_cum: (K,) cumulative uplink transmissions after iteration k
        (sum over workers of ``mask`` up to and including k).
      mask: (K, M) per-iteration transmit indicators (1 = worker uploaded).
        Under ``granularity="per_tensor"`` a 1 means "any tensor shipped".
      agg_grad_sqnorm: (K,) ||sum_m ghat_m^k||^2 — the paper's nonconvex
        progress metric, measured on the post-update bank.
      final_params: theta^K pytree.
      final_state: the full ``repro.opt.OptState`` after iteration K,
        including the stale-gradient bank and the precision-safe
        ``CommStats`` (exact uplink/downlink counts and payload bytes).
      metrics: ``()`` unless the run collected metrics
        (``collect_metrics=True``), else a ``repro.obs`` MetricBag of
        stacked per-iteration series — ``{name: (K,) array}`` (censor
        rate, exact uplink bytes, bank/gradient norms, stage-hook
        observables). Collection is read-only: every other field is
        bit-identical to a metrics-off run.
    """
    objective: jax.Array
    comm_cum: jax.Array
    mask: jax.Array
    agg_grad_sqnorm: jax.Array
    final_params: Any
    final_state: "OptState"
    metrics: Any = ()


def global_loss(task: FedTask, params) -> jax.Array:
    """f(theta) = sum_m f_m(theta)."""
    per_worker = jax.vmap(task.loss_fn, in_axes=(None, 0))(params,
                                                           task.worker_data)
    return jnp.sum(per_worker)


def trajectory(cfg: OptLike, task: FedTask, num_iters: int,
               collect_metrics: bool = False) -> History:
    """Pure (un-jitted) Algorithm-1 scan — the traceable core of ``run``.

    Args:
      cfg: a ``repro.opt`` optimizer (any ``FedOptimizer``), or the
        deprecated ``FedOptConfig`` facade. Scalar stage hyperparameters
        (alpha, beta, eps1, tau0, ...) may be traced, which is how
        ``repro.sweep`` maps one compiled program over a whole
        configuration grid; structural choices (num_workers, stage
        classes, quantize, ...) must be static.
      task: the distributed problem; ``init_params``/``worker_data`` leaves
        may themselves be traced (e.g. gathered out of a stacked task bank).
      num_iters: K, the static scan length.
      collect_metrics: also record a per-iteration ``repro.obs`` MetricBag
        in ``History.metrics`` (static — changes the scan's outputs, so it
        is part of the compiled program's identity). The bag rides
        *alongside* the optimizer state: every state-carried value is
        bit-identical to a metrics-off run (tests/test_obs.py pins this
        against the pinned runs).
    Returns:
      The full ``History`` of the run (see its docstring).

    The (params, state) scan carry is threaded through ``lax.scan``, so
    XLA reuses the carry buffers across iterations automatically — the
    per-iteration state never reallocates. Donating ``init_params`` into
    the *enclosing* jit (``run(donate=True)``, ``train/trainer.py``)
    extends that reuse to the input buffers themselves; it is safe
    because every optimizer ``init`` copies ``prev_params`` before the
    scan starts (theta^{-1} never aliases a donated theta^0).
    """
    from ..obs import compile_log
    from ..opt.compat import as_optimizer
    opt = as_optimizer(cfg)
    worker_grads_fn = jax.vmap(task.grad_fn, in_axes=(None, 0))
    # host-side tick at trace time only: how many scan programs were built
    compile_log.record("simulator", "trajectory")

    def one_iter(carry, _):
        params, state = carry
        grads = worker_grads_fn(params, task.worker_data)
        new_state, new_params, info = opt.step(state, params, grads)
        rec = (global_loss(task, params),
               new_state.comm.total_uplinks,
               info.mask,
               info.agg_grad_sqnorm)
        if collect_metrics:
            from ..obs.metrics import step_metrics
            bag_fn = getattr(opt, "metrics", None) or \
                (lambda st, sc: step_metrics(opt, st, sc))
            rec = rec + (bag_fn(new_state, info),)
        return (new_params, new_state), rec

    state0 = opt.init(task.init_params)
    (params, state), recs = jax.lax.scan(
        one_iter, (task.init_params, state0), None, length=num_iters)
    obj, comms, mask, gsq = recs[:4]
    bags = recs[4] if collect_metrics else ()
    return History(objective=obj, comm_cum=comms, mask=mask,
                   agg_grad_sqnorm=gsq, final_params=params,
                   final_state=state, metrics=bags)


def run(cfg: OptLike, task: FedTask, num_iters: int,
        jit: bool = True, collect_metrics: bool = False,
        donate: bool = False) -> History:
    """Run Algorithm 1 for ``num_iters`` iterations on one configuration.

    Args:
      cfg: one optimizer — a ``repro.opt`` composition (``opt.make`` /
        ``opt.ComposedOptimizer`` / any ``FedOptimizer``) or a deprecated
        ``FedOptConfig``.
      task: the distributed problem (see ``FedTask``).
      num_iters: number of server iterations K.
      jit: compile the scan (default); ``False`` runs eagerly for debugging.
      collect_metrics: record a per-round ``repro.obs`` MetricBag in
        ``History.metrics`` (see ``trajectory``). Off by default; turning
        it on does not change any other History field's bits.
      donate: donate ``task.init_params`` to the compiled scan so XLA can
        reuse its buffers for the scan carry (halves the peak footprint of
        the parameter-sized state). Off by default because the donated
        array is invalidated — only enable when the caller owns the task
        and will not reuse ``init_params`` afterwards. Donation never
        changes bits: ``FedOptimizer.init`` copies ``prev_params`` before
        the first step (the same guard as
        ``core.distributed.init_scan_state``), so theta^{-1} cannot alias
        a donated theta^0.
    Returns:
      ``History`` — per-iteration trajectory plus the final optimizer state.

    Note: each call traces and compiles afresh. Batched experiments should
    go through ``repro.sweep.run_sweep``, which reproduces these
    trajectories bit-exactly while compiling once for the whole grid.
    """
    def scan_all(params0):
        return trajectory(cfg, task._replace(init_params=params0), num_iters,
                          collect_metrics=collect_metrics)

    if jit:
        fn = jax.jit(scan_all, donate_argnums=(0,) if donate else ())
    else:
        fn = scan_all
    return fn(task.init_params)


def estimate_fstar(task: FedTask, alpha: float, num_iters: int = 20000,
                   beta: float = 0.9) -> jax.Array:
    """Estimate f(theta^*) by running (uncensored) heavy ball to convergence."""
    from ..opt.censor import NeverCensor
    from ..opt.optimizer import ComposedOptimizer
    from ..opt.server import HeavyBall
    from ..opt.transport import DenseTransport
    opt = ComposedOptimizer(
        censor=NeverCensor(), transport=DenseTransport(),
        server=HeavyBall(alpha, beta),
        num_workers=jax.tree_util.tree_leaves(
            task.worker_data)[0].shape[0])
    hist = run(opt, task, num_iters)
    return jnp.minimum(jnp.min(hist.objective),
                       global_loss(task, hist.final_params))


def iterations_to_accuracy(history: History, fstar, tol: float) -> int:
    """First iteration k with f(theta^k) - f* < tol, or -1."""
    err = history.objective - fstar
    hit = jnp.nonzero(err < tol, size=1, fill_value=-1)[0][0]
    return int(hit)


def comms_to_accuracy(history: History, fstar, tol: float) -> int:
    """Cumulative uplink communications when accuracy tol is first reached."""
    k = iterations_to_accuracy(history, fstar, tol)
    if k < 0:
        return -1
    return int(history.comm_cum[k])
