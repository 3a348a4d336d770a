"""In-graph metric collection: the ``MetricBag`` and its collectors.

A **MetricBag** is a flat ``dict[str, jax.Array]`` of named scalar
observables for one round — a pytree, so it rides through ``lax.scan`` /
``lax.map`` / ``vmap`` unchanged and stacks into ``(K,)`` (or ``(B, K)``)
series on the way out. The paper's primary observables (censor rate,
uplink bytes, bank/gradient norms — Figs. 1, 10-12) are all per-round
scalars, which is what makes one flat bag the right shape for every
execution surface.

Collection is strictly **read-only**: every entry is computed *from* the
optimizer state and step stats the run already produced, never fed back
into them, so a metrics-on trajectory is bit-identical to a metrics-off
one (pinned by tests/test_obs.py against the pinned runs) and the
bag can be dropped without touching the compiled step's math.

Two layers of observables:

  * **Base metrics** (:func:`step_metrics`) — what every composition
    reports: ``censor_rate``, exact cumulative ``uplink_bytes`` (derived
    from the split-int32 counters in ``core/accounting``), uplink/downlink
    counts, ``agg_grad_sqnorm``/``step_sqnorm``/``delta_sqnorm_mean``
    (free — already in ``StepStats``), and ``bank_sqnorm`` (one extra
    read-sweep over the stale bank, the only metric that costs HBM
    traffic).
  * **Stage metrics** — each censor/transport/server stage opts in via a
    ``metrics(...) -> dict`` hook; keys are namespaced by the stage's
    registry kind (``censor/stochastic/tau``, ``transport/int8/
    ef_residual_sqnorm``), so a bag is self-describing for any registered
    composition — including user-registered stages.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..core.util import tree_sqnorm

#: One round's named scalar observables (a flat pytree of () arrays).
MetricBag = Dict[str, jax.Array]


def _stage_kind(stage, table: dict[str, type]) -> str:
    """Registry kind of a stage, falling back to its lowercased class."""
    for kind, cls in table.items():
        if type(stage) is cls:
            return kind
    return type(stage).__name__.lower()


def stage_metrics(opt, state) -> MetricBag:
    """The stage-hook half of the bag, keys namespaced by registry kind.

    Calls each stage's ``metrics`` hook with its own slice of the
    optimizer state (censor state / error-feedback bank / nothing) and
    prefixes the returned keys. Stages without the hook — e.g. a custom
    class predating it — contribute nothing.
    """
    from ..opt.registry import CENSOR_KINDS, SERVER_KINDS, TRANSPORT_KINDS
    bag: MetricBag = {}
    for stage, table, ns, arg in (
            (opt.censor, CENSOR_KINDS, "censor", (state.censor,)),
            (opt.transport, TRANSPORT_KINDS, "transport", (state.err,)),
            (opt.server, SERVER_KINDS, "server", ())):
        hook = getattr(stage, "metrics", None)
        if hook is None:
            continue
        kind = _stage_kind(stage, table)
        for k, v in hook(*arg).items():
            bag[f"{ns}/{kind}/{k}"] = jnp.asarray(v)
    return bag


def step_metrics(opt, state, stats) -> MetricBag:
    """The full per-round bag for one composed step.

    Args:
      opt: the ``ComposedOptimizer`` (or anything with the three stage
        attributes) that produced the step.
      state: the post-step ``OptState``.
      stats: the step's ``StepStats``.
    Returns:
      A flat MetricBag of f32/() scalars — base metrics plus every stage
      hook's namespaced observables.
    """
    bag: MetricBag = {
        "censor_rate": 1.0 - jnp.mean(stats.mask.astype(jnp.float32)),
        "transmit_rate": jnp.mean(stats.mask.astype(jnp.float32)),
        "agg_grad_sqnorm": stats.agg_grad_sqnorm,
        "step_sqnorm": stats.step_sq,
        "delta_sqnorm_mean": jnp.mean(stats.delta_sq),
        "bank_sqnorm": tree_sqnorm(state.ghat),
    }
    bag.update(state.comm.metrics())
    bag.update(stage_metrics(opt, state))
    return bag


def merge_shard_bags(bags, weights=None) -> MetricBag:
    """Fold K per-shard MetricBags into one cohort-level bag.

    The sharded fed runtime (``fed.mesh``) collects one bag per mesh
    shard; this merges them at fold time so consumers see the same single
    bag every other surface produces. Merge rule per key, by suffix
    convention:

      * ``*rate`` / ``*mean`` — weighted mean (weights default to
        uniform; pass per-shard worker counts for exactness under uneven
        shards);
      * ``*max`` — max; ``*min`` — min;
      * everything else (counts, cumulative bytes, sqnorms of per-shard
        disjoint state) — sum.

    Cross-shard non-additive observables (``agg_grad_sqnorm`` is
    ``||sum of partials||^2``, not a sum of shard norms) must be
    overwritten by the caller with the post-fold value — the mesh runtime
    does exactly that.
    """
    bags = list(bags)
    if not bags:
        return {}
    if weights is None:
        weights = [1.0] * len(bags)
    total_w = sum(weights)
    out: MetricBag = {}
    for key in bags[0]:
        vals = [b[key] for b in bags]
        if key.endswith("rate") or key.endswith("mean"):
            out[key] = sum(w * v for w, v in zip(weights, vals)) / total_w
        elif key.endswith("max"):
            out[key] = functools.reduce(jnp.maximum, vals)
        elif key.endswith("min"):
            out[key] = functools.reduce(jnp.minimum, vals)
        else:
            out[key] = sum(vals)
    return out


def metric_names(opt, params) -> tuple[str, ...]:
    """The bag's key set for a composition, without running a step.

    Evaluates :func:`step_metrics` under ``jax.eval_shape`` on the
    iteration-0 state (zero cost, nothing compiled) — useful for schema
    checks and for writers that want a stable header before round 1.
    """
    def keys_of(p):
        state = opt.init(p)
        m = jax.tree_util.tree_leaves(state.ghat)[0].shape[0]
        from ..opt.api import StepStats
        stats = StepStats(mask=jnp.ones((m,), jnp.float32),
                          delta_sq=jnp.zeros((m,), jnp.float32),
                          step_sq=jnp.zeros((), jnp.float32),
                          agg_grad_sqnorm=jnp.zeros((), jnp.float32))
        return step_metrics(opt, state, stats)
    shapes = jax.eval_shape(keys_of, params)
    return tuple(sorted(shapes))


def summarize(series: Any, reducer=None) -> dict[str, float]:
    """Collapse a stacked ``{name: (K,) array}`` bag to final host floats.

    Args:
      series: the stacked metrics pytree a trajectory returns.
      reducer: optional ``(array) -> scalar``; default takes the last
        round's value (cumulative metrics) — pass ``np.mean`` &co for
        rate-like series.
    Returns:
      ``{name: float}`` — JSON-ready.
    """
    import numpy as np
    out = {}
    for k, v in series.items():
        arr = np.asarray(v)
        out[k] = float(reducer(arr) if reducer is not None else arr[-1])
    return out
