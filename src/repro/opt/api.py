"""The `repro.opt` federated-optimizer protocol.

The paper's Algorithm 1 is a *composition* of three orthogonal decisions:

  1. a **censor policy** — which workers upload this round (eq. 8, or any
     other novelty test: adaptive EMA thresholds, CSGD-style stochastic
     decaying thresholds, ...),
  2. a **transport** — what bits the upload carries (dense deltas, int8
     with error feedback, ...),
  3. a **server update** — how theta advances from the aggregate (plain
     gradient descent, or the eq.-(4) heavy-ball recursion).

A :class:`FedOptimizer` is anything with ``init``/``step``; the concrete
implementation shipped here (``optimizer.ComposedOptimizer``) glues one
choice of each stage together. New algorithms from the censoring literature
are new *compositions*, not new forks of the step function — see
``docs/opt_api.md`` for the 20-line tutorial.

State/stats layouts are shared with the legacy ``core.chb`` facade so the
two remain bit-interchangeable (the facade delegates here).
"""
from __future__ import annotations

from typing import TYPE_CHECKING, Any, NamedTuple, Optional, Protocol, \
    runtime_checkable

import jax
import numpy as np

if TYPE_CHECKING:   # annotation only: keeps this module import-cycle-free
    from ..core.accounting import CommStats


class OptState(NamedTuple):
    """Optimizer state threaded through every iteration of Algorithm 1.

    Attributes:
      prev_params: theta^{k-1} (the eq.-(4) momentum anchor).
      ghat: (M, ...) stale-gradient bank — worker m's last transmitted
        gradient (eq. 5 unrolled; see ``core/chb.py`` module docstring).
      err: transport state — the (M, ...) quantization error-feedback bank
        for int8, or empty leaves for dense transport.
      comm: precision-safe uplink/downlink counters (``core/accounting``).
      censor: censor-policy state — () for stateless policies (eq. 8),
        the (M,) EMA for the adaptive policy, the round counter for the
        stochastic (CSGD) policy.
    """
    prev_params: Any
    ghat: Any
    err: Any
    comm: "CommStats"
    censor: Any = ()


class StepStats(NamedTuple):
    """Per-iteration diagnostics returned by ``FedOptimizer.step``."""
    mask: jax.Array             # (M,) 1 = worker transmitted
    delta_sq: jax.Array         # (M,) ||delta_m||^2
    step_sq: jax.Array          # () ||theta^k - theta^{k-1}||^2
    agg_grad_sqnorm: jax.Array  # () ||grad_k||^2 (paper's NN metric, squared)


class ShardStepStats(NamedTuple):
    """Per-round diagnostics from ``ComposedOptimizer.shard_step``.

    All arrays are shard-local ``(M_local,)`` rows; the sharded fed runtime
    (``repro.fed.mesh``) reduces them to the scalars its quorum fold ships
    (arrived counts, loss partials). ``mask`` is the raw censor decision;
    ``attempted`` adds the participation gate (what actually hit the air —
    the comm/energy basis); ``delivered`` adds the channel gate (what the
    bank folded).
    """
    mask: jax.Array        # (M_local,) censor pass
    attempted: jax.Array   # (M_local,) censor AND participate (bytes basis)
    delivered: jax.Array   # (M_local,) attempted AND channel pass (bank fold)
    delta_sq: jax.Array    # (M_local,) ||delta_m||^2
    step_sq: jax.Array     # () ||theta^k - theta^{k-1}||^2


@runtime_checkable
class FedOptimizer(Protocol):
    """The ``repro.opt`` protocol every consumer is written against.

    ``core.simulator`` (and the trainer's scan) drive an optimizer through
    these two methods alone, so any implementation runs there. The stage
    hosts go further: ``repro.fed``'s event runtime calls the censor's
    ``client_decide`` and the transport's row entry points, and
    ``repro.sweep`` rebinds stage hyperparameters per grid point — both
    therefore require a ``ComposedOptimizer`` (or something exposing the
    same ``censor``/``transport``/``server`` attributes) and reject
    anything else with a clear error.
    """

    num_workers: int

    def init(self, params) -> OptState:
        """Build the iteration-0 state (zero bank, theta^{-1} = theta^0)."""
        ...

    def step(self, state: OptState, params, worker_grads
             ) -> tuple[OptState, Any, StepStats]:
        """One server iteration: fold censored uploads, advance theta.

        Args:
          state: current optimizer state.
          params: theta^k.
          worker_grads: pytree stacked with leading axis M — each worker's
            local gradient at theta^k.
        Returns:
          ``(new_state, new_params, stats)``.
        """
        ...


def static_pos(x) -> Optional[bool]:
    """``x > 0`` as the device decides it, for static scalars; ``None``
    when ``x`` is traced.

    The stages use this to keep *structural* decisions (does a state buffer
    exist? which censor branch compiles?) out of traced code while still
    letting hyperparameter *values* be traced by the sweep engine. The
    value is taken in the dtype JAX would give it, and a subnormal counts
    as zero: XLA flushes subnormals to zero, so that is what a traced
    ``x > 0`` decides, and a static threshold must pick the same branch.
    """
    if isinstance(x, jax.core.Tracer):
        return None
    v = np.asarray(x)
    v = v.astype(jax.dtypes.canonicalize_dtype(v.dtype))
    if np.issubdtype(v.dtype, np.floating):
        return bool(v >= np.finfo(v.dtype).tiny)
    return bool(v > 0)
