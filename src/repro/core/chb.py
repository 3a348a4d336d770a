"""Censored Heavy Ball (CHB) — DEPRECATED facade over ``repro.opt``.

One parameterized config covers the algorithm family benchmarked in the
paper:

    GD      alpha>0, beta=0,   eps1=0
    HB      alpha>0, beta>0,   eps1=0      (eq. 2)
    LAG-WK  alpha>0, beta=0,   eps1>0      (censored GD, ref. [54])
    CHB     alpha>0, beta>0,   eps1>0      (eqs. 4,5,8)

Since the ``repro.opt`` redesign the actual Algorithm-1 math lives in
composable stages (``opt.censor`` / ``opt.transport`` / ``opt.server``
glued by ``opt.ComposedOptimizer``); a ``FedOptConfig`` merely *names* one
of those compositions:

    alpha, beta        -> opt.HeavyBall(alpha, beta)
    eps1 / adaptive    -> opt.Eq8Censor / opt.AdaptiveCensor / opt.NeverCensor
    quantize           -> opt.DenseTransport / opt.Int8Transport

``init``/``step`` here delegate to that composition, bit-exactly (golden
trajectories pinned by tests/test_opt.py), and constructing a
``FedOptConfig`` emits a ``DeprecationWarning`` pointing at the new API.
New code should compose via ``repro.opt`` (``opt.make(name, ...)`` or
``opt.ComposedOptimizer(...)``) — every consumer (simulator, sweep, fed,
trainer) accepts either object.

Traced vs. static configuration fields (unchanged contract): ``alpha``,
``beta``, ``eps1`` may be traced jax scalars; ``num_workers``,
``quantize``, ``granularity``, ``bank_dtype``, ``adaptive`` must stay
static Python values.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Optional


def _static_pos(x) -> Optional[bool]:
    """``repro.opt.api.static_pos``, imported at call time: core and opt
    import each other's *submodules* lazily to stay cycle-free."""
    from ..opt.api import static_pos
    return static_pos(x)


def __getattr__(name):
    # `chb.FedOptState` / `chb.StepInfo` keep resolving for existing
    # callers; they ARE the repro.opt types now (the `ema` field of the
    # old state generalized into the policy-owned `censor` slot). Resolved
    # lazily to keep the core <-> opt import graph acyclic.
    if name in ("FedOptState", "StepInfo"):
        from ..opt.api import OptState, StepStats
        return OptState if name == "FedOptState" else StepStats
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


@dataclasses.dataclass(frozen=True)
class FedOptConfig:
    """DEPRECATED: flat-field description of one CHB-family composition.

    Prefer ``repro.opt`` (``opt.make`` / ``opt.ComposedOptimizer``); this
    facade remains so existing configs, checkpoints, and scripts keep
    working. ``alpha``/``beta``/``eps1`` may be traced scalars; all other
    fields must be static Python values. See the module docstring for the
    field -> stage mapping.
    """
    alpha: float
    num_workers: int
    beta: float = 0.0
    eps1: float = 0.0
    quantize: Optional[str] = None  # None | "int8"
    # dtype for the stale-gradient bank (bf16 halves state memory at scale)
    bank_dtype: Any = None
    # BEYOND PAPER: relative-novelty EMA censoring (opt.AdaptiveCensor)
    adaptive: float = 0.0
    adaptive_decay: float = 0.9
    # BEYOND PAPER: censoring granularity, "global" | "per_tensor"
    granularity: str = "global"

    def __post_init__(self):
        warnings.warn(
            "FedOptConfig is deprecated: compose optimizers via repro.opt "
            "instead (opt.make(name, alpha, num_workers, ...) or "
            "opt.ComposedOptimizer); FedOptConfig is now a thin facade "
            "that builds the same composition.",
            DeprecationWarning, stacklevel=3)

    @property
    def name(self) -> str:
        ep, bp = _static_pos(self.eps1), _static_pos(self.beta)
        if ep is None or bp is None:
            return "swept"     # traced fields: the family is decided on-device
        if ep and bp:
            return "chb"
        if ep:
            return "lag"
        if bp:
            return "hb"
        return "gd"

    def build(self):
        """The ``opt.ComposedOptimizer`` this config describes."""
        from ..opt.compat import from_config
        return from_config(self)


def init(cfg: FedOptConfig, params) -> "FedOptState":
    """DEPRECATED: ``cfg.build().init(params)`` (kept for callers)."""
    return cfg.build().init(params)


def step(cfg: FedOptConfig, state, params, worker_grads):
    """DEPRECATED: one Algorithm-1 iteration via the composed optimizer.

    Returns ``(new_params, new_state, StepInfo)`` — the legacy return
    order (the ``repro.opt`` protocol returns state first).
    """
    new_state, new_params, stats = cfg.build().step(
        state, params, worker_grads)
    return new_params, new_state, stats
