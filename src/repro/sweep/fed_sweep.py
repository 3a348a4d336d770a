"""Edge-scenario sweeps: repro.fed deployment knobs as one device program.

The event-driven runtime (``repro.fed.runner``) is host-side Python — ideal
for wall-clock fidelity, hopeless for dense scenario grids. This module
models the same deployment knobs in *vmappable synchronous rounds* so a
whole (loss rate × participation × quorum × seed) grid runs as a single
jitted scan, sharing the engine's partition/export machinery.

Synchronous-round semantics (each a documented simplification of the event
runtime, reducing to it exactly in the ideal case):

  * participation — each client independently joins the round's cohort with
    probability ``participation`` (the event runtime samples a fixed-size
    cohort; i.i.d. Bernoulli is the vmappable analogue).
  * censoring — cohort members apply the exact eq.-(8) test against the
    current step norm, as in ``chb.step``.
  * loss — each transmission drops i.i.d. with ``loss_prob``; a dropped
    uplink costs air bytes/energy but leaves the server bank and quorum
    count untouched (censored zero-byte beacons do count toward quorum).
  * quorum — the server applies the eq.-(4) update only when
    ``#arrived >= ceil(quorum * #cohort)``; a failed round folds any
    delivered deltas into the bank (they arrived) but freezes theta.

Correctness anchor (tests/test_fed_sweep in tests/test_sweep.py): the ideal
point (loss 0, participation 1, quorum 1) reproduces
``core/simulator.run`` trajectories bit-exactly.
"""
from __future__ import annotations

import dataclasses
import itertools
import json
from typing import Any, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.censoring import delta_sqnorms, step_sqnorm
from ..core.quantize import payload_bytes_dense
from ..core.simulator import FedTask, global_loss
from ..core.util import tree_sqnorm, tree_stack_zeros, tree_sum_leading
from ..fed.energy import EnergyModel
from ..opt import AdaptiveCensor, as_optimizer
from ..opt.transport import _bcast


class FedScenarioPoint(NamedTuple):
    """One deployment scenario inside a fed sweep.

    Attributes:
      loss_prob: i.i.d. uplink drop probability.
      participation: per-client per-round cohort-join probability.
      quorum: fraction of the cohort that must arrive before theta advances.
      seed: PRNG seed for the scenario's participation/loss draws.
    """
    loss_prob: float = 0.0
    participation: float = 1.0
    quorum: float = 1.0
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class FedScenarioGrid:
    """Cartesian product over deployment knobs (all traced axes).

    Args:
      loss_prob / participation / quorum / seed: axis values; the product
        is enumerated row-major in this field order.
    """
    loss_prob: Sequence[float] = (0.0,)
    participation: Sequence[float] = (1.0,)
    quorum: Sequence[float] = (1.0,)
    seed: Sequence[int] = (0,)

    def points(self) -> tuple[FedScenarioPoint, ...]:
        return tuple(
            FedScenarioPoint(float(l), float(p), float(q), int(s))
            for l, p, q, s in itertools.product(
                self.loss_prob, self.participation, self.quorum, self.seed))


def run_fed_sweep(cfg, task: FedTask,
                  grid, num_rounds: int, *,
                  energy: Optional[EnergyModel] = None,
                  vectorize: bool = False,
                  mesh=None) -> "FedSweepResult":
    """Sweep deployment scenarios for one algorithm as one device program.

    Args:
      cfg: the algorithm shared by every scenario — a ``repro.opt``
        optimizer (or legacy ``FedOptConfig``); must use a dense transport,
        ``granularity="global"``, and a non-adaptive censor policy (the
        modes the synchronous-round model covers; the adaptive EMA's
        cohort-wide state update is ill-defined under partial
        participation).
      task: the distributed problem.
      grid: a ``FedScenarioGrid`` or explicit ``FedScenarioPoint`` sequence.
      num_rounds: synchronous server rounds R per scenario.
      energy: radio/compute energy model for the per-point accounting
        (defaults to ``fed.EnergyModel()``).
      vectorize: as in ``run_sweep`` — ``False`` (lax.map) keeps the ideal
        point bit-exact vs ``simulator.run``; ``True`` batches for speed.
      mesh: optional 1-D device mesh (``launch.mesh.make_client_mesh``):
        the scenario grid is partitioned over its devices — scenarios are
        embarrassingly parallel, so each shard runs its contiguous block
        of points with the same per-point program and the results are
        bit-identical to the unpartitioned sweep at any shard count
        (tests/test_distributed.py pins this). The grid size must divide
        the shard count.
    Returns:
      A ``FedSweepResult`` with objective/uplink/bytes/energy trajectories
      per scenario.
    """
    opt = as_optimizer(cfg)
    if getattr(opt, "censor", None) is None or \
            getattr(opt, "server", None) is None:
        raise TypeError(
            "run_fed_sweep drives the censor/server stages directly, so "
            "it needs a ComposedOptimizer (or an optimizer exposing those "
            f"stage attributes), not {type(opt).__name__}")
    if opt.quantize is not None:
        raise NotImplementedError("fed sweep supports dense transport only")
    if opt.granularity != "global":
        raise NotImplementedError("fed sweep supports granularity='global'")
    if isinstance(opt.censor, AdaptiveCensor):
        raise NotImplementedError("fed sweep does not cover adaptive mode")
    points = grid.points() if isinstance(grid, FedScenarioGrid) \
        else tuple(grid)
    m = jax.tree_util.tree_leaves(task.worker_data)[0].shape[0]
    if opt.num_workers != m:
        raise ValueError(f"cfg.num_workers={opt.num_workers} != task M={m}")
    energy = energy if energy is not None else EnergyModel()

    worker_grads_fn = jax.vmap(task.grad_fn, in_axes=(None, 0))

    def one_scenario(point):
        loss_p, part, quo, seed = point

        def one_round(carry, _):
            params, prev, ghat, key, cstate = carry
            key, k_part, k_drop = jax.random.split(key, 3)
            participate = (jax.random.uniform(k_part, (m,)) < part
                           ).astype(jnp.float32)
            grads = worker_grads_fn(params, task.worker_data)
            delta = jax.tree_util.tree_map(
                lambda g, h: g.astype(h.dtype) - h, grads, ghat)
            dsq = delta_sqnorms(delta)
            ssq = step_sqnorm(params, prev)
            censor_pass, new_cstate = opt.censor.decide(cstate, dsq, ssq)
            # repro-lint: disable=mask-multiply-select -- both operands are
            # 0/1 masks, so this is a boolean AND, not a payload select
            transmit = participate * censor_pass
            dropped = (jax.random.uniform(k_drop, (m,)) < loss_p
                       ).astype(jnp.float32) * transmit
            delivered = transmit - dropped
            # deliveries always fold (eq. 5 stale-bank semantics); quorum
            # only gates the theta update, exactly like the event runtime
            new_ghat = jax.tree_util.tree_map(
                lambda h, q: h + _bcast(delivered, h) * q.astype(h.dtype),
                ghat, delta)
            agg = tree_sum_leading(new_ghat)
            upd = opt.server.apply(params, prev, agg)
            arrived = participate - dropped     # beacons count, drops don't
            cohort = jnp.sum(participate)
            met = (jnp.sum(arrived) >= jnp.ceil(quo * cohort)) & (cohort > 0)
            new_params = jax.tree_util.tree_map(
                lambda u, t: jnp.where(met, u, t), upd, params)
            new_prev = jax.tree_util.tree_map(
                lambda t, tp: jnp.where(met, t, tp), params, prev)
            rec = (global_loss(task, params), tree_sqnorm(agg),
                   transmit.astype(jnp.int8), delivered.astype(jnp.int8),
                   participate.astype(jnp.int8), met)
            return (new_params, new_prev, new_ghat, key, new_cstate), rec

        p0 = task.init_params
        ghat0 = tree_stack_zeros(p0, m)
        key0 = jax.random.PRNGKey(seed)
        _, recs = jax.lax.scan(
            one_round, (p0, p0, ghat0, key0, opt.censor.init(m)), None,
            length=num_rounds)
        return recs

    ftype = jnp.result_type(float)
    pts_dev = (jnp.asarray([p.loss_prob for p in points], ftype),
               jnp.asarray([p.participation for p in points], ftype),
               jnp.asarray([p.quorum for p in points], ftype),
               jnp.asarray([p.seed for p in points], jnp.uint32))
    inner = jax.vmap(one_scenario) if vectorize else \
        (lambda xs: jax.lax.map(one_scenario, xs))
    if mesh is None:
        program = jax.jit(inner)
    else:
        # scenarios are independent, so sharding the grid is a pure
        # partition: no collectives, each device scans its own block
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as _P
        axis = mesh.axis_names[0]
        n_shards = mesh.devices.size
        if len(points) % n_shards:
            raise ValueError(
                f"grid has {len(points)} points; a {n_shards}-shard mesh "
                "needs the point count divisible by the shard count — pad "
                "the grid or drop mesh=")
        pts_dev = jax.device_put(pts_dev, NamedSharding(mesh, _P(axis)))
        program = jax.jit(jax.shard_map(inner, mesh=mesh,
                                        in_specs=(_P(axis),),
                                        out_specs=_P(axis),
                                        axis_names={axis}, check_vma=False))
    obj, gsq, transmit, delivered, participate, met = \
        jax.tree_util.tree_map(np.asarray, program(pts_dev))

    # uplink and downlink ship the same dense parameter payload here
    payload = payload_bytes_dense(task.init_params)
    attempted = transmit.astype(np.int64).sum(axis=2)        # (B, R)
    cohort = participate.astype(np.int64).sum(axis=2)
    energy_per_round = energy.round_energy(attempted, cohort, payload)
    return FedSweepResult(
        points=points, num_rounds=num_rounds,
        objective=obj, agg_grad_sqnorm=gsq,
        transmit_mask=transmit, delivered_mask=delivered,
        participate_mask=participate, quorum_met=met,
        comm_cum=np.cumsum(attempted, axis=1),
        delivered_cum=np.cumsum(delivered.astype(np.int64).sum(axis=2),
                                axis=1),
        bytes_cum=np.cumsum(attempted * payload, axis=1),
        energy_cum=np.cumsum(energy_per_round, axis=1),
    )


@dataclasses.dataclass(frozen=True)
class FedSweepResult:
    """Per-scenario synchronous-round trajectories and edge accounting.

    Attributes:
      points: scenario coordinates, index-aligned with every array below.
      num_rounds: R.
      objective: (B, R) f(theta^k) before each round's update.
      agg_grad_sqnorm: (B, R) ||sum_m ghat_m||^2 at each update.
      transmit_mask / delivered_mask / participate_mask: (B, R, M) int8
        per-round indicators (attempted uplink / survived the channel /
        joined the cohort).
      quorum_met: (B, R) whether the round's theta update was applied.
      comm_cum / delivered_cum: (B, R) cumulative attempted / delivered
        uplinks.
      bytes_cum: (B, R) cumulative attempted uplink payload bytes (drops
        still burn air bytes).
      energy_cum: (B, R) cumulative radio joules (tx per attempt + rx per
        cohort member).
    """
    points: tuple[FedScenarioPoint, ...]
    num_rounds: int
    objective: np.ndarray
    agg_grad_sqnorm: np.ndarray
    transmit_mask: np.ndarray
    delivered_mask: np.ndarray
    participate_mask: np.ndarray
    quorum_met: np.ndarray
    comm_cum: np.ndarray
    delivered_cum: np.ndarray
    bytes_cum: np.ndarray
    energy_cum: np.ndarray

    def __len__(self) -> int:
        return len(self.points)

    def frontier(self, fstar: float, tol: float) -> list[dict]:
        """Edge frontier rows: rounds/uplinks/bytes/joules to accuracy.

        Args:
          fstar: optimal objective value.
          tol: target error; -1 entries mean the target was never reached.
        Returns:
          One dict per scenario, mirroring
          ``fed.runner.edge_metrics_to_accuracy``.
        """
        rows = []
        for i, p in enumerate(self.points):
            err = self.objective[i] - fstar
            hits = np.nonzero(err < tol)[0]
            if hits.size == 0:
                rec = {"rounds": -1, "uplinks": -1, "bytes": -1,
                       "energy_j": -1.0}
            else:
                k = int(hits[0])
                rec = {"rounds": k,
                       "uplinks": int(self.comm_cum[i, k]),
                       "bytes": int(self.bytes_cum[i, k]),
                       "energy_j": float(self.energy_cum[i, k])}
            rows.append({"index": i, **p._asdict(), **rec,
                         "final_err": float(err[-1])})
        return rows

    def to_json(self, path: Optional[str] = None,
                fstar: Optional[float] = None,
                tol: Optional[float] = None) -> str:
        """Serialize scenario trajectories (and optionally the frontier)."""
        doc: dict[str, Any] = {
            "num_points": len(self.points),
            "num_rounds": self.num_rounds,
            "points": [p._asdict() for p in self.points],
            "objective": self.objective.tolist(),
            "comm_cum": self.comm_cum.tolist(),
            "bytes_cum": self.bytes_cum.tolist(),
            "energy_cum": self.energy_cum.tolist(),
        }
        if fstar is not None and tol is not None:
            doc["frontier"] = self.frontier(fstar, tol)
        text = json.dumps(doc, indent=1, sort_keys=True)
        if path is not None:
            with open(path, "w") as f:
                f.write(text)
        return text
