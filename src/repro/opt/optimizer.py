"""``ComposedOptimizer`` — Algorithm 1 assembled from pluggable stages.

This is the former ``core/chb.step`` body, refactored so that the three
orthogonal decisions (censor / transport / server) are stage calls instead
of hard-wired branches. Every composition expressible by the old
``FedOptConfig`` produces a bit-identical program (pinned by
``tests/test_opt.py``'s pinned runs and the ``tests/test_sweep.py``
exactness grids); new algorithms are new compositions.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..core import accounting
from ..core.accounting import CommStats
from ..core.censoring import delta_sqnorms, step_sqnorm
from ..core.util import tree_sqnorm, tree_stack_zeros, tree_sum_leading
from ..kernels import censor as kernel_censor
from ..kernels import common as kernel_common
from ..kernels import fused_step as kernel_fused
from ..kernels import ops as kernel_ops
from .api import OptState, ShardStepStats, StepStats, static_pos
from .censor import CensorPolicy, Eq8Censor, NeverCensor
from .server import GradientDescent, HeavyBall, ServerUpdate
from .transport import DenseTransport, Int8Transport, Transport, _bcast

BACKENDS = ("reference", "pallas")


def _gate(mask, participate, channel_mask):
    """Compose the censor mask with the optional round gates.

    All operands are exact {0.0, 1.0} indicators, so the products are
    logical ANDs that stay exact — and with both gates absent the result
    IS ``mask``, keeping the ungated shard_step bit-identical to step.
    """
    attempted_mask = mask if participate is None else mask * participate
    delivered_mask = attempted_mask if channel_mask is None \
        else attempted_mask * channel_mask
    return attempted_mask, delivered_mask


def _worker_axis_leaves(init, params):
    """Which leaves of the state ``init(params, k)`` builds carry the
    worker axis: those whose shape changes with the worker count ``k``."""
    shapes = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    one, two = (jax.eval_shape(lambda p, k=k: init(p, k), shapes)
                for k in (1, 2))
    return jax.tree_util.tree_map(lambda a, b: a.shape != b.shape, one,
                                  two)


@dataclasses.dataclass(frozen=True)
class ComposedOptimizer:
    """One censor policy + one transport + one server update.

    Structural fields (``num_workers``, ``granularity``, ``bank_dtype``,
    ``backend``, and each stage's *class*) decide the compiled program and
    must be static; the stages' scalar hyperparameters (alpha, beta, eps1,
    tau0) may be traced — which is how ``repro.sweep`` runs a whole grid
    of compositions through one compiled program.

    Attributes:
      censor: who uploads (``opt.censor``).
      transport: what the upload carries (``opt.transport``).
      server: how theta advances (``opt.server``).
      num_workers: M.
      granularity: ``"global"`` (the paper's single-vector view) or
        ``"per_tensor"`` (beyond paper: the eq.-(8) test per parameter
        tensor; requires an :class:`~repro.opt.censor.Eq8Censor` with a
        static eps1 and a dense transport).
      bank_dtype: optional dtype for the stale-gradient bank (bf16 halves
        state memory at scale).
      backend: ``"reference"`` (pure-jnp stage calls) or ``"pallas"``
        (the fused ``repro.kernels`` execution engine: one-sweep censor
        sqnorms over the stacked bank, fused bank advance, fused int8 +
        error feedback, fused eq.-(4) update). Numerics contract, for
        f32/f64 params: every fused stage runs the reference's exact
        expressions in the reference's dtypes, so steps agree up to XLA
        fusion/reduction-order ulps — and are **bit-identical on the
        pinned golden tasks** (tests/test_backend.py); see
        ``docs/kernels.md`` for the precise statement and its limits on
        large tensors. Sub-f32 params (bf16/f16) instead upcast to f32
        inside the kernels — better accumulation than the reference's
        native-bf16 arithmetic, matching the ``ref.py`` oracles but NOT
        the reference backend. Requires a fusable transport (the
        built-in dense / int8 / topk / lowrank, or any stateful
        transport providing ``encode_feedback_pallas``) and gd/hb
        servers — other custom stages have no fused path and must run
        on the reference backend.
    """

    censor: CensorPolicy
    transport: Transport
    server: ServerUpdate
    num_workers: int
    granularity: str = "global"
    bank_dtype: Any = None
    backend: str = "reference"

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(
                f"unknown backend {self.backend!r}; valid: {BACKENDS}")
        if self.backend == "pallas":
            # the fused kernels implement the built-in stages only; a
            # custom stage silently falling back would misreport what ran.
            # A stateful transport opts into the fused step by providing
            # ``encode_feedback_pallas`` (int8/topk/lowrank do); stateless
            # ones must be the dense passthrough (the fused path never
            # calls their encode).
            fusable = isinstance(self.transport, DenseTransport) or (
                self.transport.stateful
                and hasattr(self.transport, "encode_feedback_pallas"))
            if not fusable:
                raise TypeError(
                    "backend='pallas' fuses the built-in transports "
                    "(dense | int8 | topk | lowrank) and stateful "
                    "transports providing encode_feedback_pallas; custom "
                    f"transport {type(self.transport).__name__} must run "
                    "on the reference backend")
            if not isinstance(self.server, (GradientDescent, HeavyBall)):
                raise TypeError(
                    "backend='pallas' fuses the built-in servers "
                    "(gd | hb); custom server "
                    f"{type(self.server).__name__} must run on the "
                    "reference backend")

    # ------------------------------------------------ hyperparameter views
    # Flat views of the stages' scalars, matching the legacy FedOptConfig
    # field names so hyperparameter-only consumers (core/distributed, the
    # sweep grid) read either object interchangeably.
    @property
    def alpha(self):
        return self.server.alpha

    @property
    def beta(self):
        return getattr(self.server, "beta", 0.0)

    @property
    def eps1(self):
        return getattr(self.censor, "eps1", 0.0)

    @property
    def adaptive(self):
        return getattr(self.censor, "adaptive", 0.0)

    @property
    def quantize(self) -> Optional[str]:
        return self.transport.mode

    @property
    def name(self) -> str:
        """gd/hb/lag/chb classification (paper Sec. II), or "swept"."""
        ep, bp = static_pos(self.eps1), static_pos(self.beta)
        if ep is None or bp is None:
            return "swept"
        if ep and bp:
            return "chb"
        if ep:
            return "lag"
        if bp:
            return "hb"
        return "gd"

    def with_hparams(self, *, alpha=None, beta=None,
                     eps1=None) -> "ComposedOptimizer":
        """Rebind scalar hyperparameters (possibly with traced values).

        This is the sweep engine's hook: one composition is built per
        static partition, then each grid point rebinds (alpha, beta, eps1)
        with device scalars.

        * ``beta`` rebinds a momentum server; a momentum-free server
          (``GradientDescent``) is promoted to ``HeavyBall(alpha, beta)``,
          which is bit-identical at beta=0 — so a ``lag``/``gd`` base
          sweeps exactly like the equivalent legacy config did.
        * ``eps1`` retargets an eq.-(8) censor (or upgrades a
          ``NeverCensor`` to one). Any other policy — adaptive,
          stochastic, or a custom one — keeps its own thresholds
          untouched (the engine's eps axis does not describe them; sweep
          their knobs via named ``GridPoint(algo=...)`` points instead).
        """
        server = self.server
        if alpha is not None:
            server = dataclasses.replace(server, alpha=alpha)
        if beta is not None:
            if hasattr(server, "beta"):
                server = dataclasses.replace(server, beta=beta)
            else:
                server = HeavyBall(server.alpha, beta)
        censor = self.censor
        if eps1 is not None:
            if isinstance(censor, Eq8Censor):
                censor = dataclasses.replace(censor, eps1=eps1)
            elif isinstance(censor, NeverCensor):
                censor = Eq8Censor(eps1)
            # other policies own their thresholds: leave them as composed
        return dataclasses.replace(self, censor=censor, server=server)

    # ----------------------------------------------------------- protocol
    def init(self, params) -> OptState:
        """Build the iteration-0 state (zero bank, theta^{-1} = theta^0)."""
        bank = tree_stack_zeros(params, self.num_workers)
        if self.bank_dtype is not None:
            bank = jax.tree_util.tree_map(
                lambda x: x.astype(self.bank_dtype), bank)
        # copy: prev_params must not alias params, mirroring the step-0
        # guard in core/distributed.init_scan_state — callers jit the step
        # with params AND state donated (train/trainer.py,
        # simulator.run(donate=True)), and two donated views of one buffer
        # would let XLA overwrite theta^0 while it is still theta^{-1}
        prev = jax.tree_util.tree_map(jnp.copy, params)
        return OptState(
            prev_params=prev,
            ghat=bank,
            err=self.transport.init(params, self.num_workers),
            comm=CommStats.init(self.num_workers),
            censor=self.censor.init(self.num_workers),
        )

    @property
    def bank_tiles(self) -> bool:
        """Whether ``shard_step`` can hold the bank as the kernels' tiles.

        True for the pallas backend's stateless (dense) transport at
        global granularity: there only the two staged bank kernels and
        the eq.-(5) worker-sum read the bank, so it can stay in the
        ``(M, R, 128)`` form those kernels take (``shard_init``) instead
        of being padded into it and sliced back out every round.
        """
        return (self.backend == "pallas" and not self.transport.stateful
                and self.granularity == "global")

    def shard_init(self, params) -> OptState:
        """The shard-local state ``shard_step`` takes: ``init``, with the
        bank leaves as ``(M, R, 128)`` kernel tiles where ``bank_tiles``.
        """
        state = self.init(params)
        if not self.bank_tiles:
            return state
        return state._replace(ghat=jax.tree_util.tree_map(
            kernel_common._pad_to_3d, state.ghat))

    def metrics(self, state: OptState, stats: StepStats):
        """Per-round ``repro.obs`` MetricBag for a completed step.

        Read-only: every entry is derived from ``state``/``stats`` (plus
        each stage's ``metrics`` hook on its own state slice), so
        collecting never perturbs the trajectory. See
        ``repro.obs.metrics.step_metrics`` for the bag's contents.
        """
        from ..obs import metrics as obs_metrics
        return obs_metrics.step_metrics(self, state, stats)

    def step(self, state: OptState, params, worker_grads
             ) -> tuple[OptState, Any, StepStats]:
        """One iteration of Algorithm 1 (see ``api.FedOptimizer.step``)."""
        with jax.named_scope(f"chb_step[{self.backend}]"):
            return self._step(state, params, worker_grads)

    def _step(self, state: OptState, params, worker_grads
              ) -> tuple[OptState, Any, StepStats]:
        # per_tensor granularity binds to the eq.-(8) censor only; any other
        # policy (never / adaptive / stochastic) degenerates to the global
        # path, mirroring the legacy eps1==0 behavior.
        if self.granularity == "per_tensor" and \
                isinstance(self.censor, Eq8Censor):
            eps_pos = static_pos(self.censor.eps1)
            if eps_pos is None:
                raise NotImplementedError(
                    "per_tensor censoring needs a static eps1 (its byte "
                    "accounting divmods the payload host-side)")
            if eps_pos:
                delta = jax.tree_util.tree_map(
                    lambda g, h: g.astype(h.dtype) - h,
                    worker_grads, state.ghat)
                pending = self.transport.prepare(delta, state.err)
                return self._step_per_tensor(state, params, pending)

        if self.backend == "pallas":
            return self._step_pallas(state, params, worker_grads)

        # delta_m = g_m - ghat_m (in the bank's dtype for exact sync)
        delta = jax.tree_util.tree_map(
            lambda g, h: g.astype(h.dtype) - h, worker_grads, state.ghat)
        pending = self.transport.prepare(delta, state.err)
        dsq = delta_sqnorms(pending)
        ssq = step_sqnorm(params, state.prev_params)
        mask, new_censor = self.censor.decide(state.censor, dsq, ssq)

        payload, aux = self.transport.encode(pending, state.err)
        new_err = self.transport.feedback(mask, pending, payload, aux,
                                          state.err)
        per_tx_bytes = self.transport.payload_bytes(params)

        # server/worker synchronized advance of the stale bank
        new_ghat = jax.tree_util.tree_map(
            lambda h, q: h + _bcast(mask, h) * q.astype(h.dtype),
            state.ghat, payload)

        # grad_k = sum_m ghat_m^k  (== eq. (5) recursion unrolled)
        agg = tree_sum_leading(new_ghat)
        new_params = self.server.apply(params, state.prev_params, agg)

        stats = StepStats(mask=mask, delta_sq=dsq, step_sq=ssq,
                          agg_grad_sqnorm=tree_sqnorm(agg))
        new_state = OptState(
            prev_params=params,
            ghat=new_ghat,
            err=new_err,
            comm=state.comm.update(mask, per_tx_bytes),
            censor=new_censor,
        )
        return new_state, new_params, stats

    def _step_pallas(self, state: OptState, params, worker_grads
                     ) -> tuple[OptState, Any, StepStats]:
        """The fused-kernel execution of the global-granularity step.

        Stage semantics are identical to the reference path — same censor
        ``decide``, same accounting, same state layout — but every
        parameter-sized sweep runs through ``repro.kernels``:

          * eq.-(8) left-hand side: one fused sweep per leaf over the
            stacked bank (dense transports never materialize the delta
            tree at all);
          * bank advance: one fused ``ghat + mask * delta`` sweep;
          * stateful transports: the transport's own
            ``encode_feedback_pallas`` route — int8 runs a per-worker
            abs-max reduction plus ONE fused sweep emitting payload and
            error-feedback bank together; top-k packs its keep selection
            and the EF update in one fused sweep; low-rank fuses the
            residual/EF blend after its (jnp, MXU-bound) factor matmuls;
          * eq. (4): the one-sweep heavy-ball kernel with traced
            alpha/beta SMEM operands.

        Numerics at f32/f64: per-element expressions and dtypes match
        the reference path exactly; what may differ is XLA's fusion of
        the jnp side (FMA contraction on large tensors) and the tiled
        partial-sum order of the sqnorm reductions — both ulp-level per
        step. On the paper-scale tasks masks, counts, objective, params
        and bank are bit-identical to the reference; the diagnostic
        ``agg_grad_sqnorm``, a sum of squares each program fuses its own
        way, agrees to a few ulps (tests/exactness.py). On much larger
        tensors trajectories can drift by compounded ulps while censor
        masks and uplink counts stay aligned (see docs/kernels.md).
        Sub-f32 params compute in f32 in-kernel and therefore genuinely
        diverge from the reference's native-bf16 arithmetic (they match
        the ``ref.py`` oracles instead).
        """
        # fused megakernel routing (kernels/fused_step.py): dense and
        # int8+EF run the whole post-``decide`` tail as ONE sweep per
        # leaf; topk/lowrank (host-graph top_k / factor matmuls between
        # the elementwise stages) keep the staged path. The flag is
        # consulted at trace time — ``fused_step.force_staged()`` pins a
        # program to the staged kernels for A/B comparison.
        fused = kernel_fused.fusion_enabled()
        int8_fused = fused and type(self.transport) is Int8Transport
        quantized = self.transport.stateful
        dense_fused = fused and not quantized
        pending = scales = None
        if int8_fused:
            # sweep 1: sqnorm + abs-max partials from an in-register
            # pending recompute — the pending tree is never materialized
            dsq, scales = kernel_ops.tree_int8_stats(
                worker_grads, state.ghat, state.err)
        elif quantized:
            delta = jax.tree_util.tree_map(
                lambda g, h: g.astype(h.dtype) - h,
                worker_grads, state.ghat)
            pending = self.transport.prepare(delta, state.err)
            dsq = kernel_ops.tree_sqnorms(pending)
        else:
            dsq = kernel_ops.tree_delta_sqnorms(worker_grads, state.ghat)
        ssq = step_sqnorm(params, state.prev_params)
        mask, new_censor = self.censor.decide(state.censor, dsq, ssq)

        alpha = self.server.alpha
        beta = getattr(self.server, "beta", 0.0)
        if dense_fused:
            new_err = state.err
            new_ghat, agg, new_params = kernel_ops.tree_fused_dense_step(
                worker_grads, state.ghat, params, state.prev_params, mask,
                alpha, beta)
        elif int8_fused:
            new_ghat, new_err, agg, new_params = \
                kernel_ops.tree_fused_int8_step(
                    worker_grads, state.ghat, state.err, params,
                    state.prev_params, mask, scales, alpha, beta)
        else:
            if quantized:
                payload, new_err = self.transport.encode_feedback_pallas(
                    pending, state.err, mask)
                new_ghat = kernel_ops.tree_bank_advance(state.ghat,
                                                        payload, mask)
            else:
                new_err = state.err
                new_ghat = kernel_ops.tree_censor_bank_advance(
                    worker_grads, state.ghat, mask)
            agg = tree_sum_leading(new_ghat)
            new_params = self.apply_server(params, state.prev_params, agg)
        per_tx_bytes = self.transport.payload_bytes(params)
        stats = StepStats(mask=mask, delta_sq=dsq, step_sq=ssq,
                          agg_grad_sqnorm=tree_sqnorm(agg))
        new_state = OptState(
            prev_params=params,
            ghat=new_ghat,
            err=new_err,
            comm=state.comm.update(mask, per_tx_bytes),
            censor=new_censor,
        )
        return new_state, new_params, stats

    def shard_step(self, state: OptState, params, worker_grads, *,
                   worker_ids=None, participate=None, channel_mask=None,
                   capacity: int = 0
                   ) -> tuple[OptState, Any, ShardStepStats]:
        """The client-side half of a step, for ONE mesh shard.

        This is ``step`` with the server update factored out: it runs the
        censor/transport stages and the bank advance for a shard-local
        block of workers and returns the shard's eq.-(5) **partial**
        aggregate ``sum_m ghat_m`` instead of new params. The sharded fed
        runtime (``repro.fed.mesh``) folds the K partials with a single
        ``psum`` (``core.distributed.make_client_fold``) and advances
        theta once via ``apply_server`` — over one shard with no gates,
        the composed program is bit-identical to ``step`` (the sync
        anchor; partial + identity-psum + apply is the same HLO as
        ``step``'s agg + apply).

        Args:
          state: SHARD-LOCAL state from ``shard_init`` (``(M_local, ...)``
            bank rows, as kernel tiles where ``bank_tiles``; the shard's
            own CommStats; replicated censor state).
          params / worker_grads: theta^k (replicated) and the shard's
            ``(M_local, ...)`` stacked gradients — or, with ``capacity``,
            a function from ``(capacity,)`` shard-local row indices to
            those rows' stacked gradients (an index ``>= M_local`` marks
            an empty slot, whose gradient is never used).
          worker_ids: the shard's absolute global client ids — draw-keyed
            censors fold these so the masks are invariant to how the
            population is split (omit for a single full-population shard).
          participate: optional (M_local,) {0,1} gate — who woke up this
            round. Censor-passing non-participants do NOT transmit.
          channel_mask: optional (M_local,) {0,1} gate — whose uplink
            survived the channel. Transmissions that drop still spend
            bytes/energy (``attempted``) but never reach the bank
            (``delivered``), matching ``sweep.fed_sweep`` semantics.
          capacity: when positive, step only the participants: they are
            gathered in blocks of this many rows (as many blocks as the
            draw needs, one in the usual case), and each block's gradients,
            bank rows and transport-state rows go through the same per-row
            stages, then back into place. Every other row's state is left
            as it is, which is what the full step does to a non-participant
            (``_gate`` zeroes its attempt and delivery). Needs
            ``participate`` and a censor whose decisions are per client
            (``supports_event_runtime``). Global state (``prev_params``,
            censor state, the global counters) advances once, and the
            partial is summed over the whole bank, as without it.
        Returns:
          ``(new_state, partial_agg, ShardStepStats)``. With ``capacity``
          the stats' ``mask`` and ``delta_sq`` read 0 off the cohort.
        """
        if self.granularity != "global":
            raise NotImplementedError(
                "shard_step supports global granularity only (per_tensor "
                "byte accounting is host-side and unsharded)")
        ssq = step_sqnorm(params, state.prev_params)
        if capacity:
            return self._shard_step_cohort(
                state, params, worker_grads, ssq, capacity,
                worker_ids=worker_ids, participate=participate,
                channel_mask=channel_mask)
        new_ghat, new_err, new_censor, stats = self._shard_rows(
            state, worker_grads, ssq, worker_ids, participate, channel_mask)
        return self._shard_finish(state, params, new_ghat, new_err,
                                  new_censor, stats)

    def _shard_rows(self, state: OptState, worker_grads, ssq, worker_ids,
                    participate, channel_mask, *, hbm: bool = False):
        """The per-row stages of ``shard_step`` over the rows ``state``
        holds: eq.-(8) norms, the censor decision, the gates, encode and
        error feedback, the bank advance. Returns ``(new_ghat, new_err,
        new_censor, ShardStepStats)``. ``hbm`` holds the tiled kernels'
        operands in HBM (``kernels.common.in_hbm``)."""
        if self.backend == "pallas":
            return self._shard_rows_pallas(state, worker_grads, ssq,
                                           worker_ids, participate,
                                           channel_mask, hbm=hbm)
        delta = jax.tree_util.tree_map(
            lambda g, h: g.astype(h.dtype) - h, worker_grads, state.ghat)
        pending = self.transport.prepare(delta, state.err)
        dsq = delta_sqnorms(pending)
        mask, new_censor = self._decide(state.censor, dsq, ssq, worker_ids)
        attempted_mask, delivered_mask = _gate(mask, participate,
                                               channel_mask)

        payload, aux = self.transport.encode(pending, state.err)
        new_err = self.transport.feedback(delivered_mask, pending, payload,
                                          aux, state.err)
        new_ghat = jax.tree_util.tree_map(
            lambda h, q: h + _bcast(delivered_mask, h) * q.astype(h.dtype),
            state.ghat, payload)
        stats = ShardStepStats(mask=mask, attempted=attempted_mask,
                               delivered=delivered_mask, delta_sq=dsq,
                               step_sq=ssq)
        return new_ghat, new_err, new_censor, stats

    def _shard_rows_pallas(self, state: OptState, worker_grads, ssq,
                           worker_ids, participate, channel_mask, *,
                           hbm: bool = False):
        """Staged-kernel ``_shard_rows``. The megakernel is out of reach
        here — it fuses the eq.-(4) update into the sweep, and the server
        half of a sharded round runs after the cross-shard fold — so this
        path always takes the staged kernels (sqnorm sweeps, fused
        encode+EF, fused bank advance), matching ``_step_pallas`` with
        ``force_staged()`` minus the server apply. With ``bank_tiles`` the
        gradient is tiled once, both kernels run on tiles, and the bank
        advances in place."""
        quantized = self.transport.stateful
        bank_tiles = self.bank_tiles
        pending = None
        if bank_tiles:
            worker_grads = jax.tree_util.tree_map(kernel_common._pad_to_3d,
                                                  worker_grads)
        if quantized:
            delta = jax.tree_util.tree_map(
                lambda g, h: g.astype(h.dtype) - h,
                worker_grads, state.ghat)
            pending = self.transport.prepare(delta, state.err)
            dsq = kernel_ops.tree_sqnorms(pending)
        else:
            dsq = kernel_ops.tree_delta_sqnorms(worker_grads, state.ghat,
                                                tiles=bank_tiles, hbm=hbm)
        mask, new_censor = self._decide(state.censor, dsq, ssq, worker_ids)
        attempted_mask, delivered_mask = _gate(mask, participate,
                                               channel_mask)

        if quantized:
            payload, new_err = self.transport.encode_feedback_pallas(
                pending, state.err, delivered_mask)
            new_ghat = kernel_ops.tree_bank_advance(state.ghat, payload,
                                                    delivered_mask)
        else:
            new_err = state.err
            new_ghat = kernel_ops.tree_censor_bank_advance(
                worker_grads, state.ghat, delivered_mask, tiles=bank_tiles,
                hbm=hbm)
        stats = ShardStepStats(mask=mask, attempted=attempted_mask,
                               delivered=delivered_mask, delta_sq=dsq,
                               step_sq=ssq)
        return new_ghat, new_err, new_censor, stats

    def _shard_finish(self, state: OptState, params, new_ghat, new_err,
                      new_censor, stats: ShardStepStats):
        """The once-a-round half of ``shard_step``: the eq.-(5) partial
        over the whole shard bank, and the new state."""
        partial = tree_sum_leading(new_ghat)
        if self.bank_tiles:  # only the (R, 128) sum leaves the tile form
            partial = jax.tree_util.tree_map(
                lambda p, t: kernel_common.untile(p, t.shape),
                partial, params)
        new_state = OptState(
            prev_params=params,
            ghat=new_ghat,
            err=new_err,
            comm=state.comm.update(stats.attempted,
                                   self.transport.payload_bytes(params)),
            censor=new_censor,
        )
        return new_state, partial, stats

    def _shard_step_cohort(self, state: OptState, params, grad_rows, ssq,
                           capacity: int, *, worker_ids, participate,
                           channel_mask):
        """``shard_step(capacity=C)``: the per-row stages on the
        participants only, in blocks of C gathered rows.

        The participants' shard-local rows, in order, are cut into blocks
        of C; a ``while_loop`` steps one block per trip (the first always
        runs, so a round with no participant still advances the censor
        state). Empty slots index past the shard: their gathers clip, their
        gates are 0 and their scatters are dropped. Each block reads the
        round's starting censor state and ``prev_params``, as every row
        does in the full step, and a per-client censor decides each row
        from that row alone, so any split into blocks gives the full
        step's decisions and rows."""
        if participate is None:
            raise ValueError("shard_step(capacity=...) steps the "
                             "participants, so it needs participate")
        if not getattr(self.censor, "supports_event_runtime", False):
            raise ValueError(
                f"{type(self.censor).__name__} decides over the whole "
                "cohort, so its rows cannot be stepped apart")
        m, c = self.num_workers, int(capacity)
        # every block start j*C < max(n, 1) <= m keeps j*C + C <= m + C
        order = jnp.nonzero(participate, size=m + c, fill_value=m)[0]
        n = jnp.sum((participate != 0).astype(jnp.int32))
        blocks = jnp.maximum(1, (n + c - 1) // c)
        row_leaves = _worker_axis_leaves(self.transport.init, params)

        def take(x, rows):
            return jnp.take(x, rows, axis=0, mode="clip")

        def put(x, rows, v):
            return x.at[rows].set(v, mode="drop")

        def trip(carry):
            j, ghat, err, att, dlv, msk, dsq, _ = carry
            rows = jax.lax.dynamic_slice(order, (j * c,), (c,))
            ids = rows if worker_ids is None else take(worker_ids, rows)
            sub = state._replace(
                ghat=jax.tree_util.tree_map(lambda x: take(x, rows), ghat),
                err=jax.tree_util.tree_map(
                    lambda x, r: take(x, rows) if r else x, err, row_leaves))
            gate = None if channel_mask is None else take(channel_mask, rows)
            # a block's kernels read their tiles from HBM, as the full
            # round's do, and not from a VMEM copy the compiler would make
            # of operands this small (kernels.common.in_hbm)
            g_ghat, g_err, censor, st = self._shard_rows(
                sub, grad_rows(rows), ssq, ids,
                (rows < m).astype(jnp.float32), gate, hbm=True)
            ghat = jax.tree_util.tree_map(
                lambda x, v: put(x, rows, v), ghat, g_ghat)
            err = jax.tree_util.tree_map(
                lambda x, v, r: put(x, rows, v) if r else v,
                err, g_err, row_leaves)
            return (j + 1, ghat, err, put(att, rows, st.attempted),
                    put(dlv, rows, st.delivered), put(msk, rows, st.mask),
                    put(dsq, rows, st.delta_sq), censor)

        zeros = jnp.zeros((m,), jnp.float32)
        _, ghat, err, att, dlv, msk, dsq, censor = jax.lax.while_loop(
            lambda carry: carry[0] < blocks, trip,
            (jnp.zeros((), blocks.dtype), state.ghat, state.err, zeros,
             zeros, zeros, zeros, state.censor))
        stats = ShardStepStats(mask=msk, attempted=att, delivered=dlv,
                               delta_sq=dsq, step_sq=ssq)
        return self._shard_finish(state, params, ghat, err, censor, stats)

    def _decide(self, censor_state, dsq, ssq, worker_ids):
        if worker_ids is None:
            return self.censor.decide(censor_state, dsq, ssq)
        return self.censor.decide_ids(censor_state, dsq, ssq, worker_ids)

    def apply_server(self, params, prev_params, agg):
        """The backend-dispatched server update (``repro.fed`` hook).

        The event runtime calls this instead of ``server.apply`` so a
        pallas composition advances theta through the fused eq.-(4)
        kernel there too. ``GradientDescent`` runs the kernel at beta=0,
        which is bit-identical to its reference delegation by
        construction.
        """
        if self.backend == "pallas":
            return kernel_ops.tree_hb_update(
                params, prev_params, agg, self.server.alpha,
                getattr(self.server, "beta", 0.0))
        return self.server.apply(params, prev_params, agg)

    def _step_per_tensor(self, state: OptState, params, pending):
        """Per-tensor censoring (beyond paper; see class docstring).

        The eq.-(8) test is applied independently per parameter tensor;
        uplink bytes are accounted per transmitted tensor, uplink *count*
        counts a worker-iteration as transmitting if ANY tensor ships (so
        the headline count stays comparable with global censoring).
        Quantization/error-feedback is not combined with this mode.
        """
        assert not self.transport.stateful, \
            "per_tensor + quantized transport not supported"
        eps1 = self.censor.eps1
        leaves_delta, treedef = jax.tree_util.tree_flatten(pending)
        leaves_theta = treedef.flatten_up_to(params)
        leaves_prev = treedef.flatten_up_to(state.prev_params)
        leaves_ghat = treedef.flatten_up_to(state.ghat)

        m = self.num_workers
        new_ghat = []
        mib_up = jnp.zeros((), jnp.int32)
        rem_up = jnp.zeros((), jnp.int32)
        any_mask = jnp.zeros((m,), jnp.float32)
        pallas = self.backend == "pallas"
        for d, t, tp, h in zip(leaves_delta, leaves_theta, leaves_prev,
                               leaves_ghat):
            if pallas:          # fused per-leaf eq.-(8) partials
                dsq_t = kernel_censor.sqnorm_batched(d)          # (M,)
            else:
                dsq_t = jnp.sum(
                    jnp.square(d.astype(jnp.float32)).reshape(m, -1),
                    axis=1)                                      # (M,)
            ssq_t = jnp.sum(jnp.square(t.astype(jnp.float32)
                                       - tp.astype(jnp.float32)))
            mask_t = (dsq_t > eps1 * ssq_t).astype(jnp.float32)
            any_mask = jnp.maximum(any_mask, mask_t)
            n_tx_t = jnp.sum(mask_t).astype(jnp.int32)
            # exact split-counter byte accounting (accounting.py): leaf
            # payload is static, so divmod happens in Python; carry per
            # leaf keeps the traced remainder below int32 range
            pb_mib, pb_rem = accounting.split_bytes(
                d[0].size * d.dtype.itemsize)
            mib_up, rem_up = accounting.carry_bytes(
                mib_up + n_tx_t * pb_mib, rem_up + n_tx_t * pb_rem)
            if pallas:          # fused bank advance, one sweep per leaf
                new_ghat.append(kernel_censor.bank_advance(h, d, mask_t))
            else:
                new_ghat.append(h + _bcast(mask_t, h) * d.astype(h.dtype))
        new_ghat = jax.tree_util.tree_unflatten(treedef, new_ghat)

        agg = tree_sum_leading(new_ghat)
        new_params = self.apply_server(params, state.prev_params, agg)
        comm = CommStats(
            uplink_count=state.comm.uplink_count + any_mask.astype(jnp.int32),
            uplink_mib=state.comm.uplink_mib,
            uplink_rem=state.comm.uplink_rem,
            downlink_count=state.comm.downlink_count + 1,
            iterations=state.comm.iterations + 1,
        ).add_bytes_split(mib_up, rem_up)
        stats = StepStats(mask=any_mask,
                          delta_sq=delta_sqnorms(pending),
                          step_sq=step_sqnorm(params, state.prev_params),
                          agg_grad_sqnorm=tree_sqnorm(agg))
        new_state = OptState(prev_params=params, ghat=new_ghat,
                             err=state.err, comm=comm, censor=state.censor)
        return new_state, new_params, stats
