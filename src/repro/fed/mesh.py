"""Mesh-sharded synchronous federated runtime: 10^5–10^6 clients per sweep.

The event runtime (``fed.runner``) walks a host-side event heap — perfect
wall-clock fidelity, hopeless past ~10^3 clients. This module runs the
same deployment knobs as synchronous rounds with the **client axis as a
first-class sharded leading axis**: every client bank (stale-gradient
``ghat``, EF residual, censor state, comm counters) lives as per-shard
blocks on a 1-D ``("clients",)`` mesh (``launch.mesh.make_client_mesh``),
each device runs one jitted round program over its contiguous client
block, and the shards meet at the server through a single ``psum`` fold
(``core.distributed.make_client_fold``) carrying the eq.-(5) partial
aggregates plus the quorum/loss scalars. Nothing client-sized ever
crosses the shard boundary — the fold traffic is one parameter-sized
pytree plus five scalars per round, independent of M.

Round semantics are exactly ``sweep.fed_sweep``'s (i.i.d. Bernoulli
participation and uplink loss, censoring via the composed policy,
deliveries always folding into the bank, quorum gating only the theta
update — see ``fed.runner.quorum_need`` for the shared quorum
definition), but draws are **per-client key-folded** by absolute client
id instead of drawn from a split chain, which is what makes the masks
invariant to the shard count.

Two exactness anchors (pinned by tests/test_fed_mesh.py and the
multi-device legs in tests/test_distributed.py; contracts stated in
docs/fed_scaling.md):

  (a) **sync anchor** — the ideal scenario (participation 1, loss 0,
      quorum 1) sharded over ONE device computes ``core.simulator.run``'s
      run: masks, uplink counts, objective and final params bit-identical;
      the diagnostic ``agg_grad_sqnorm``, a sum of squares the two
      programs fuse differently, within a few ulps.
  (b) **K-invariance** — the same run over K shards draws the *same*
      participation/loss/censor decisions for every client (masks
      bit-equal for K in {1, 2, 8}); float trajectories agree to the
      reduction-order ulps of the K-way partial-sum fold.

Anchor (b) deliberately batches each shard's gradient evaluations with
``jax.vmap`` over the **contiguous block** rather than the ``lax.map``
the draw-exact doctrine usually demands: vmapped row math is bit-stable
under *splitting a leading axis into contiguous blocks* (the only
regrouping sharding performs), which experiment-validated bitwise at
K in {1, 2, 4, 8}, while a per-client ``lax.map`` is NOT bit-identical
to the vmapped ``simulator.run`` grads and would break anchor (a). The
inline lint suppressions below carry that argument.

Where participation is drawn and small (``cohort_capacity``), a shard
round draws first and computes only the drawn writers, gathered in blocks
of C rows; draws, masks, counts and bytes stay those of the round that
steps every writer (docs/fed_scaling.md, "Cohort rounds").
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..core.distributed import make_client_fold
from ..core.simulator import FedTask
from ..core.util import tree_sqnorm
from ..launch.mesh import make_client_mesh
from ..launch.sharding import (client_shard_sizes, per_device_views,
                               replicated_sharding, stack_shards)
from ..lint import draw_exact
from ..obs import profile
from ..opt import AdaptiveCensor, as_optimizer
from ..opt.api import StepStats
from .channel import ChannelConfig
from .clients import Population, VectorPopulation
from .energy import EnergyModel


@dataclasses.dataclass(frozen=True)
class MeshScenario:
    """One deployment scenario for the mesh runtime.

    Same knobs and semantics as ``sweep.fed_sweep.FedScenarioPoint``:
    ``participation`` is the per-client per-round i.i.d. cohort-join
    probability, ``loss_prob`` the i.i.d. uplink drop probability,
    ``quorum`` the arrived fraction gating the theta update, ``seed``
    keys every draw. Draws are folded per (seed, round, client-id), so a
    scenario replays identically at any shard count.
    """
    participation: float = 1.0
    loss_prob: float = 0.0
    quorum: float = 1.0
    seed: int = 0

    def __post_init__(self):
        if not 0.0 < self.participation <= 1.0:
            raise ValueError("participation must be in (0, 1]")
        if not 0.0 <= self.loss_prob < 1.0:
            raise ValueError("loss_prob must be in [0, 1)")
        if not 0.0 < self.quorum <= 1.0:
            raise ValueError("quorum must be in (0, 1]")

    @property
    def sync_draws(self) -> bool:
        """True when no participation/loss randomness exists — the round
        programs then compile with NO RNG ops at all (the sync-anchor
        fast path; quorum is trivially met but still evaluated)."""
        return self.participation >= 1.0 and self.loss_prob == 0.0


class MeshHistory(NamedTuple):
    """Per-round trajectory + cohort accounting of one ``run_mesh``.

    Counts are exact (int32 in-graph sums of {0,1} indicators, int64
    host-side cumulatives); bytes are exact Python-int products of the
    static per-uplink payload.
    """
    objective: np.ndarray        # (R,) f(theta^k) before round k's update
    agg_grad_sqnorm: np.ndarray  # (R,) ||sum_m ghat_m||^2 at the update
    quorum_met: np.ndarray       # (R,) bool — theta advanced this round
    participated: np.ndarray     # (R,) cohort size per round
    attempted: np.ndarray        # (R,) uplinks attempted (censor & cohort)
    delivered: np.ndarray        # (R,) uplinks that survived the channel
    comm_cum: np.ndarray         # (R,) cumulative attempted uplinks
    delivered_cum: np.ndarray    # (R,) cumulative delivered uplinks
    bytes_cum: np.ndarray        # (R,) cumulative attempted payload bytes
    energy_cum: np.ndarray       # (R,) cumulative joules (radio + compute)
    wall_clock: np.ndarray       # (R,) modeled seconds at end of round k
    final_params: Any            # replicated global array pytree
    mask: Optional[np.ndarray] = None     # (R, M) int8 attempted-uplink rows
    metrics: tuple = ()          # per-round merged MetricBags (host floats)


def make_server_round(opt, mesh, quorum: float):
    """The server half of a round: ``server_round(stacked, params, prev)``.

    Folds the ``(K, ...)`` stacked shard partials with the one ``psum``
    (``core.distributed.make_client_fold``), evaluates the quorum, and
    advances theta where it is met. Returns ``(new_params, new_prev, met,
    loss_sum, agg_grad_sqnorm, n_part, n_att, n_del, comp_j)``.
    """
    fold = make_client_fold(mesh)
    # every shard applies the server update to its own replica: the pallas
    # backend's update is a Mosaic kernel, which the compiler cannot
    # partition across devices itself
    apply_server = jax.shard_map(
        opt.apply_server, mesh=mesh, in_specs=P(), out_specs=P(),
        axis_names=set(mesh.axis_names), check_vma=False)

    def server_round(stacked, params, prev):
        partial_agg, loss_sum, n_part, n_att, n_del, comp_j = fold(stacked)
        # beacons count toward quorum, drops don't: arrived =
        # participated - (attempted - delivered), as in fed_sweep
        arrived = n_part - (n_att - n_del)
        met = (arrived.astype(jnp.float32)
               >= jnp.ceil(jnp.asarray(quorum, jnp.float32)
                           * n_part.astype(jnp.float32))) & (n_part > 0)
        upd = apply_server(params, prev, partial_agg)
        new_params = jax.tree_util.tree_map(
            lambda u, t: jnp.where(met, u, t), upd, params)
        new_prev = jax.tree_util.tree_map(
            lambda t, tp: jnp.where(met, t, tp), params, prev)
        return (new_params, new_prev, met, loss_sum,
                tree_sqnorm(partial_agg), n_part, n_att, n_del, comp_j)

    return server_round


def _gather_rows(x, rows):
    """``x[rows]`` along the writer axis (a row past the end reads the
    last), as one slice per row joined together.

    Written for the TPU compiler, at the population's full size: its
    gather first cuts every writer's row into lane-aligned pieces of the
    whole array, and a loop writing rows into a buffer re-lays the whole
    array out; a join of row slices does neither. Float rows go through
    as their bits, behind a barrier: a float join lets the compiler round
    every writer's data for the gradient's matmul once the join sits in
    the block loop, instead of the C rows it needs.
    """
    if not jnp.issubdtype(x.dtype, jnp.floating):
        return jnp.take(x, rows, axis=0, mode="clip")
    # a start past the end clamps to the last row, as every slice's does
    picked = [jax.lax.dynamic_slice_in_dim(x, r, 1,
                                           allow_negative_indices=False)
              for r in rows]
    word = jnp.dtype(f"uint{8 * x.dtype.itemsize}")
    joined = jnp.concatenate([jax.lax.bitcast_convert_type(p, word)
                              for p in picked])
    return jax.lax.bitcast_convert_type(
        jax.lax.optimization_barrier(joined), x.dtype)


def cohort_capacity(m_local: int, scenario: MeshScenario, censor, *,
                    collect_metrics: bool = False) -> int:
    """Rows per block of a shard round's gathered cohort, or 0 for the
    round that steps every writer.

    ``C = ceil8(m p + 6 sqrt(m p (1 - p)))`` for ``m_local = m`` writers
    drawn at participation ``p``: six standard deviations over the mean of
    the Binomial(m, p) cohort, so a draw past it (exact still, by a second
    block) is rare. The gathered round runs only where it pays and stays
    exact: where draws exist (not ``sync_draws``), ``C <= m_local / 2``,
    the censor decides per client (``supports_event_runtime``), and no
    metric bag is collected (its entries read every writer's norms).
    """
    if scenario.sync_draws or collect_metrics or \
            not getattr(censor, "supports_event_runtime", False):
        return 0
    p = scenario.participation
    mean = m_local * p
    c = 8 * math.ceil((mean + 6.0 * math.sqrt(mean * (1.0 - p))) / 8.0)
    return c if 2 * c <= m_local else 0


def make_shard_round(opt, task: FedTask, scenario: MeshScenario, *,
                     collect_metrics: bool = False):
    """The client half of a round for one shard of ``opt.num_workers``.

    ``shard_round(state, params, data, ids, comp_s, compw_s, round_idx)``
    computes the shard's gradients, draws, ``shard_step`` and losses, and
    returns ``(new_state, stacked_row, attempted, wall_local[, bag])``:
    ``stacked_row`` is the shard's ``(1, ...)`` row of the fold's
    ``(partial_agg, loss_sum, n_part, n_att, n_del, comp_j)``. ``state``
    comes from ``opt.shard_init``, so where ``opt.bank_tiles`` it holds
    its bank as kernel tiles, and the round keeps it so.

    Where ``cohort_capacity`` gives C > 0 the round draws first and
    computes gradients for the drawn writers only: ``shard_step(capacity=
    C)`` gathers them in blocks of C rows and scatters their state back.
    The loss that makes the objective still covers every writer.
    """
    m_local = opt.num_workers
    part_p, loss_p = scenario.participation, scenario.loss_prob
    sync_draws, seed = scenario.sync_draws, scenario.seed
    capacity = cohort_capacity(m_local, scenario, opt.censor,
                               collect_metrics=collect_metrics)

    @draw_exact
    def shard_round(state, params, data, ids, comp_s, compw_s, round_idx):
        if not capacity:
            # the contiguous-block vmap: bit-stable under resplitting the
            # leading axis (the only regrouping sharding performs) and
            # identical to simulator.run's batching — see module docstring
            # repro-lint: disable=vmap-in-draw-exact -- contiguous-block
            # vmap is the anchor-(a) batching; lax.map would break
            # bit-identity with simulator.run's vmapped grads
            grads = jax.vmap(task.grad_fn, in_axes=(None, 0))(params, data)
        if sync_draws:
            participate = channel_mask = None
        else:
            rkey = jax.random.fold_in(jax.random.PRNGKey(seed), round_idx)

            def draws(cid):
                ck = jax.random.fold_in(rkey, cid)
                return (jax.random.uniform(jax.random.fold_in(ck, 0)),
                        jax.random.uniform(jax.random.fold_in(ck, 1)))

            # repro-lint: disable=vmap-in-draw-exact -- each lane's
            # draw is keyed by (seed, round, absolute client id)
            # alone, so batching cannot regroup or leak across lanes
            u_part, u_drop = jax.vmap(draws)(ids)
            participate = (u_part < part_p).astype(jnp.float32)
            channel_mask = (u_drop >= loss_p).astype(jnp.float32)
        if capacity:
            def grads(rows):
                block = jax.tree_util.tree_map(
                    lambda x: _gather_rows(x, rows), data)
                # repro-lint: disable=vmap-in-draw-exact -- the drawn
                # cohort's rows: each writer's gradient reads its own row
                # alone, and tests/test_cohort_gather.py holds masks and
                # counts exact to the contiguous-block round
                return jax.vmap(task.grad_fn, in_axes=(None, 0))(params,
                                                                 block)
        new_state, partial_agg, st = opt.shard_step(
            state, params, grads, worker_ids=ids, participate=participate,
            channel_mask=channel_mask, capacity=capacity)
        # repro-lint: disable=vmap-in-draw-exact -- same
        # contiguous-block batching as the grads; the per-shard sum
        # is the psum partial
        losses = jax.vmap(task.loss_fn, in_axes=(None, 0))(params, data)
        loss_part = jnp.sum(losses)
        if participate is None:
            n_part = jnp.asarray(m_local, jnp.int32)
            comp_active = comp_s
        else:
            n_part = jnp.sum(participate.astype(jnp.int32))
            comp_active = jnp.where(participate != 0, comp_s, 0.0)
        n_att = jnp.sum(st.attempted.astype(jnp.int32))
        n_del = jnp.sum(st.delivered.astype(jnp.int32))
        wall_local = jnp.max(comp_active) if m_local else \
            jnp.zeros((), jnp.float32)
        comp_j = jnp.sum(comp_active * compw_s)
        partials = (partial_agg, loss_part, n_part, n_att, n_del, comp_j)
        stacked_row = jax.tree_util.tree_map(lambda v: v[None], partials)
        out = (new_state, stacked_row, st.attempted, wall_local)
        if collect_metrics:
            from ..obs.metrics import step_metrics
            bag = step_metrics(opt, new_state, StepStats(
                mask=st.mask, delta_sq=st.delta_sq, step_sq=st.step_sq,
                agg_grad_sqnorm=tree_sqnorm(partial_agg)))
            out = out + (bag,)
        return out

    return shard_round


def run_mesh(cfg, task: FedTask, num_rounds: int, *,
             mesh=None,
             scenario: Optional[MeshScenario] = None,
             population: Optional[VectorPopulation] = None,
             channel: Optional[ChannelConfig] = None,
             energy: Optional[EnergyModel] = None,
             collect_mask: bool = True,
             collect_metrics: bool = False,
             donate: bool = False,
             bake_data: bool = True) -> MeshHistory:
    """Run one scenario with the client axis sharded over ``mesh``.

    Args:
      cfg: the composed optimizer (any transport/backend with a
        ``shard_step`` path: dense/int8/topk/lowrank on both backends);
        adaptive censoring is rejected for consistency with
        ``sweep.fed_sweep`` (its cohort-wide EMA is ill-defined under
        partial participation).
      task: the distributed problem; ``worker_data``'s leading axis M
        must equal ``cfg.num_workers`` and divide the shard count.
      num_rounds: synchronous server rounds R.
      mesh: a ``("clients",)`` mesh from ``launch.mesh.make_client_mesh``
        (default: 1 shard). Each device owns the contiguous client block
        ``[i*M/K, (i+1)*M/K)``.
      scenario: deployment knobs (default: the ideal sync scenario).
      population: optional columnar per-client compute model
        (``VectorPopulation``, or a ``Population`` — converted via
        ``as_vector``) driving the wall-clock and compute-energy models;
        its ``participation`` field is ignored here —
        ``scenario.participation`` governs the draws.
      channel: nominal air-interface for the wall-clock model (rates and
        overhead only; its ``loss_prob``/fading knobs are ignored —
        ``scenario.loss_prob`` governs drops). Default: ideal.
      energy: radio/compute energy model (default ``EnergyModel()``).
      collect_mask: record the (R, M) attempted-uplink rows (exact masks
        for the anchor tests; turn off at 10^6 clients to keep host
        memory flat).
      collect_metrics: collect one merged ``repro.obs`` MetricBag per
        round (per-shard bags folded via ``obs.metrics.merge_shard_bags``
        with the cross-shard ``agg_grad_sqnorm`` overwritten post-fold).
      donate: donate each shard's state buffers into its round program —
        the (M_local, ...) banks are the dominant memory at scale, and
        donation lets XLA reuse them across rounds.
      bake_data: fold each shard's data block into its round program as a
        compile-time constant (one trace per shard) instead of passing it
        as a jit argument (one shared trace). Baked data is what
        ``simulator.run``'s scan does with its closed-over
        ``worker_data``, and only then is anchor (a)'s objective bitwise:
        on dot-product tasks XLA contracts a *constant* operand
        differently from a parameter operand by ~1 ulp, so with
        argument-passed data the floats are ``allclose`` to the scan
        while draws, masks and counts stay identical. Pass ``False`` at
        10^5+ clients, where constant-folding the data bloats the
        executable and compile time; element-wise tasks
        (``data.edge_tasks.make_edge_quadratics``) lose nothing either
        way, and the K-invariance anchor (b) holds in both modes.
    Returns:
      A ``MeshHistory``.

    The host reads each round's results one round late, in one batched
    fetch: it dispatches round k, then fetches round k-1's server scalars,
    slowest compute times, mask rows and bags with one ``jax.device_get``
    and accounts for them, so the devices have round k queued meanwhile.
    The last round's results are drained the same way before the call
    returns. Only those host-bound outputs are held across the lag, never
    a shard's state, which the next round takes (donated under
    ``donate``); the history is the one a serial loop builds.
    """
    with profile.annotate("run_mesh"):
        opt = as_optimizer(cfg)
        if getattr(opt, "censor", None) is None or \
                getattr(opt, "server", None) is None:
            raise TypeError(
                "run_mesh drives the censor/transport stages through "
                "shard_step, so it needs a ComposedOptimizer (or an "
                "optimizer exposing the stage attributes), not "
                f"{type(opt).__name__}")
        if opt.granularity != "global":
            raise NotImplementedError(
                "run_mesh supports granularity='global'")
        if isinstance(opt.censor, AdaptiveCensor):
            raise NotImplementedError(
                "run_mesh rejects adaptive censoring (cohort-wide EMA is "
                "ill-defined under partial participation; see fed_sweep)")
        # the pallas dense route keeps each shard's bank as the kernels'
        # tiles from set-up on (ComposedOptimizer.bank_tiles / shard_init)
        tiled_leaves = len(jax.tree_util.tree_leaves(task.init_params)) \
            if opt.bank_tiles else 0
        scenario = scenario if scenario is not None else MeshScenario()
        m = jax.tree_util.tree_leaves(task.worker_data)[0].shape[0]
        if opt.num_workers != m:
            raise ValueError(
                f"cfg.num_workers={opt.num_workers} != task M={m}")
        mesh = mesh if mesh is not None else make_client_mesh(1)
        m_local = client_shard_sizes(m, mesh)
        # rows of a shard round's gathered cohort (0: every writer stepped)
        cohort_rows = cohort_capacity(m_local, scenario, opt.censor,
                                      collect_metrics=collect_metrics)
        with profile.annotate("run_mesh/setup", tiled_leaves=tiled_leaves,
                              cohort_rows=cohort_rows):
            if isinstance(population, Population):
                population = population.as_vector()
            channel = channel if channel is not None else \
                ChannelConfig.ideal()
            energy = energy if energy is not None else EnergyModel()
            if population is not None and population.num_clients != m:
                raise ValueError(
                    f"population has {population.num_clients} clients, "
                    f"task has {m}")
            devices = list(mesh.devices.flat)
            k_shards = len(devices)

            # -------------------------------------- per-shard constant data
            def _block(x, i):
                return x[i * m_local:(i + 1) * m_local]

            data_blocks, ids_blocks = [], []
            comp_blocks, compw_blocks = [], []
            comp = np.zeros((m,), np.float32) if population is None else \
                np.asarray(population.compute_mean_s, np.float32)
            compw = np.zeros((m,), np.float32) if population is None else \
                np.asarray(population.compute_w, np.float32)
            for i, dev in enumerate(devices):
                data_blocks.append(jax.device_put(jax.tree_util.tree_map(
                    lambda x: _block(x, i), task.worker_data), dev))
                ids_blocks.append(jax.device_put(
                    jnp.arange(i * m_local, (i + 1) * m_local,
                               dtype=jnp.uint32), dev))
                comp_blocks.append(jax.device_put(_block(comp, i), dev))
                compw_blocks.append(jax.device_put(_block(compw, i), dev))

            opt_local = dataclasses.replace(opt, num_workers=m_local)
            shard_round = make_shard_round(opt_local, task, scenario,
                                           collect_metrics=collect_metrics)
            donate_args = (0,) if donate else ()
            if bake_data:
                def _baked(d, ii):
                    def fn(state, params, comp_s, compw_s, round_idx):
                        return shard_round(state, params, d, ii, comp_s,
                                           compw_s, round_idx)
                    return jax.jit(fn, donate_argnums=donate_args)
                progs = [_baked(data_blocks[i], ids_blocks[i])
                         for i in range(k_shards)]

                def run_shard(i, state, pview, k):
                    return progs[i](state, pview, comp_blocks[i],
                                    compw_blocks[i], np.int32(k))
            else:
                shard_prog = jax.jit(shard_round, donate_argnums=donate_args)

                def run_shard(i, state, pview, k):
                    return shard_prog(state, pview, data_blocks[i],
                                      ids_blocks[i], comp_blocks[i],
                                      compw_blocks[i], np.int32(k))

            # --------------------------------------- fold + server program
            rep = replicated_sharding(mesh)
            server_round = make_server_round(opt, mesh, scenario.quorum)
            server_prog = jax.jit(server_round, out_shardings=rep)
            copy_tree = jax.jit(
                lambda t: jax.tree_util.tree_map(jnp.copy, t))

            # ------------------------------------------------- shard states
            params_rep = jax.device_put(task.init_params, rep)
            prev_rep = jax.device_put(
                jax.tree_util.tree_map(jnp.copy, task.init_params), rep)
            states = []
            for i, dev in enumerate(devices):
                params_dev = jax.device_put(task.init_params, dev)
                states.append(jax.jit(opt_local.shard_init)(params_dev))

            payload = opt.transport.payload_bytes(task.init_params)
            uplink_air = 0.0
            if np.isfinite(channel.uplink_rate_bps):
                uplink_air = channel.overhead_s + 8.0 * payload / \
                    channel.uplink_rate_bps
            downlink_air = channel.downlink_time(payload)

        objective, gsq_hist, met_hist = [], [], []
        n_part_h, n_att_h, n_del_h = [], [], []
        wall, energy_cum, t, joules = [], [], 0.0, 0.0
        mask_rows: list[np.ndarray] = []
        bags: list[dict] = []

        def dispatch(k):
            """Enqueue round k; return what the host reads of it, with
            its transfers started: the server's seven scalars, then per
            shard its slowest client's compute time, its mask rows and its
            bag. Never a shard's state, which round k+1 takes (donated)."""
            nonlocal states, params_rep, prev_rep
            params_views = per_device_views(params_rep, mesh)
            outs = [run_shard(i, states[i], params_views[i], k)
                    for i in range(k_shards)]
            stacked = stack_shards([o[1] for o in outs], mesh)
            (params_rep, prev_rep, *scalars) = server_prog(
                stacked, params_rep, prev_rep)
            # shard states carry theta^{k-1} for the next eq.-(8) step
            # norm; quorum may have frozen it, so overwrite from the
            # server's (replicated) new_prev. Copy under donation: the raw
            # per-device views alias prev_rep's buffers, which the next
            # round would donate away while server_round still needs them.
            prev_views = per_device_views(prev_rep, mesh)
            states = [o[0]._replace(prev_params=copy_tree(pv) if donate
                                    else pv)
                      for o, pv in zip(outs, prev_views)]
            kept = (scalars, [o[3] for o in outs],
                    [o[2] for o in outs] if collect_mask else [],
                    [o[4] for o in outs] if collect_metrics else [])
            for x in jax.tree_util.tree_leaves(kept):
                x.copy_to_host_async()
            return kept

        def sync(k, kept):
            """Round k's host values, in one batched fetch; ``ahead`` says
            whether the round was still running, so the chip had the next
            round queued while the host waited."""
            leaves = jax.tree_util.tree_leaves(kept)
            ahead = int(not all(x.is_ready() for x in leaves))
            with profile.annotate("run_mesh/sync", round=k,
                                  reads=len(leaves), fetches=1,
                                  ahead=ahead):
                return jax.device_get(kept)

        def account(got):
            nonlocal t, joules
            ((met, loss, gsq, n_part, n_att, n_del, comp_j), walls,
             shard_rows, shard_bags) = got
            part_h, att_h = int(n_part), int(n_att)
            # one shard's cohort is the round's: the blocks it took
            chunks = {} if not cohort_rows or k_shards > 1 else {
                "cohort_chunks": max(1, -(-part_h // cohort_rows))}
            with profile.annotate("run_mesh/account", **chunks):
                objective.append(float(loss))
                gsq_hist.append(float(gsq))
                met_hist.append(bool(met))
                n_part_h.append(part_h)
                n_att_h.append(att_h)
                n_del_h.append(int(n_del))
                t += (max(float(w) for w in walls)
                      + (uplink_air if att_h else 0.0) + downlink_air)
                wall.append(t)
                joules += float(energy.round_energy(
                    att_h, part_h, payload)) + float(comp_j)
                energy_cum.append(joules)
                if collect_mask:
                    mask_rows.append(
                        np.concatenate(shard_rows).astype(np.int8))
                if collect_metrics:
                    from ..obs.metrics import merge_shard_bags
                    merged = merge_shard_bags(
                        shard_bags, weights=[m_local] * k_shards)
                    merged = {kk: float(np.asarray(v))
                              for kk, v in merged.items()}
                    merged["agg_grad_sqnorm"] = gsq_hist[-1]
                    bags.append(merged)

        # The readback runs one round late: round k's span dispatches
        # round k, then reads round k-1's values in one batched fetch and
        # accounts for them, so the chip has round k queued while the host
        # waits; the last round's span drains its own. None of those
        # values feeds a later round.
        pending = None
        for k in range(num_rounds):
            with profile.step("run_mesh/round", k):
                with profile.annotate("run_mesh/dispatch"):
                    kept = dispatch(k)
                if pending is not None:
                    account(sync(k - 1, pending))
                pending = kept
                if k == num_rounds - 1:
                    account(sync(k, pending))

        with profile.annotate("run_mesh/history"):
            att = np.asarray(n_att_h, np.int64)
            return MeshHistory(
                objective=np.asarray(objective),
                agg_grad_sqnorm=np.asarray(gsq_hist),
                quorum_met=np.asarray(met_hist, bool),
                participated=np.asarray(n_part_h, np.int64),
                attempted=att,
                delivered=np.asarray(n_del_h, np.int64),
                comm_cum=np.cumsum(att),
                delivered_cum=np.cumsum(np.asarray(n_del_h, np.int64)),
                bytes_cum=np.cumsum(att * payload),
                energy_cum=np.asarray(energy_cum),
                wall_clock=np.asarray(wall),
                final_params=params_rep,
                mask=np.stack(mask_rows) if mask_rows else None,
                metrics=tuple(bags),
            )
