"""Device-resident sweep engine: a whole ConfigGrid as one compiled program.

Every grid point is one Algorithm-1 run (``core/simulator.trajectory``).
Instead of re-tracing and re-jitting ``simulator.run`` per point — which is
what made dense hyperparameter frontiers dispatch-bound — the engine:

  1. partitions the grid by its *static* axes (num_workers, quantize,
     seed, named ``algo``; plus eps1 under per-tensor granularity), which
     genuinely change the compiled program;
  2. inside each partition, stacks the *traced* axes (alpha, beta, eps1)
     into device arrays and maps the pure trajectory over them with
     ``lax.map`` (default) or ``vmap`` (``vectorize=True``);
  3. jits each partition once, so a 32-point grid pays one compilation
     instead of 32.

Exactness contract: the default ``lax.map`` execution traces the per-point
program with exactly the shapes ``simulator.run`` uses, so trajectories are
**bit-identical** to per-point runs of the same backend, diagnostics
included (asserted by tests/test_sweep.py through tests/exactness.py).
Across backends a sweep holds the two-program contract: decisions and
trajectory bitwise, the f32 ``agg_grad_sqnorm`` within a few ulps.
``vectorize=True`` batches the gradient matmuls across points, which is
faster for large grids of tiny problems but perturbs float reductions by
~1 ulp per iteration — enough to flip an occasional f32 censor decision
near the numerical floor. Use it when throughput matters more than
bit-reproducibility.

Seeds: multiple ``seed`` values require a ``task_factory(seed, num_workers)
-> FedTask``. Task data is closed over as program constants, as
``simulator.run`` does; passing the data as a program argument, or
gathering it from a stacked bank, perturbs XLA's matmul lowering by ~1 ulp,
so the rows would no longer be bitwise those of per-point runs. Each
distinct seed is therefore its own compiled partition: a 16-point eps-grid
over 2 seeds compiles twice instead of 32 times.
"""
from __future__ import annotations

import dataclasses
import json
import time
import warnings
from typing import Any, Callable, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from .. import opt as opt_mod
from ..core import simulator
from ..lint import draw_exact
from ..core.simulator import FedTask, History
from ..opt import (ComposedOptimizer, DenseTransport, Eq8Censor, HeavyBall,
                   NeverCensor, as_optimizer)
from ..opt.registry import _transport
from .grid import ConfigGrid, GridPoint

TaskFactory = Callable[[int, int], FedTask]


def _leading_dim(task: FedTask) -> int:
    return jax.tree_util.tree_leaves(task.worker_data)[0].shape[0]


def _float_dtype():
    return jnp.result_type(float)   # f64 under jax_enable_x64, else f32


def _base_optimizer(base_cfg, m: int) -> ComposedOptimizer:
    """The partition's template composition (num_workers not yet bound)."""
    if base_cfg is None:
        return ComposedOptimizer(
            censor=NeverCensor(), transport=DenseTransport(),
            server=HeavyBall(0.0, 0.0), num_workers=m)
    base = as_optimizer(base_cfg)
    if not isinstance(base, ComposedOptimizer):
        raise TypeError(
            "base_cfg must be a ComposedOptimizer (or a legacy "
            "FedOptConfig); arbitrary FedOptimizers have no sweepable "
            f"(alpha, beta, eps1) hooks: {type(base).__name__}")
    return base


def _named_axes(p: GridPoint) -> tuple[bool, bool]:
    """Which optional grid axes a named-``algo`` point explicitly set.

    ``GridPoint``'s 0.0 defaults mean "unset" for named points: a default
    axis is *omitted* from the builder call so the algorithm's registered
    defaults apply (``GridPoint(algo="chb")`` must run the paper's chb,
    not a beta=0/eps1=0 impostor labeled chb). The flags are part of the
    partition key — they change which scalars the compiled program traces.
    """
    return (p.beta != 0.0, p.eps1 != 0.0)


def _point_optimizer(p: GridPoint, m: int, base_cfg,
                     *, alpha=None, beta=None, eps1=None) -> ComposedOptimizer:
    """The optimizer a grid point describes.

    Called twice per point: host-side with concrete floats (for
    ``SweepResult.specs``) and inside the trace with device scalars (the
    ``alpha``/``beta``/``eps1`` overrides). Named-``algo`` points build
    through the registry; continuum points rebind the template's
    hyperparameters.
    """
    alpha = p.alpha if alpha is None else alpha
    beta = p.beta if beta is None else beta
    eps1 = p.eps1 if eps1 is None else eps1
    if p.algo is not None:
        beta_set, eps_set = _named_axes(p)
        kw = {"quantize": p.quantize, "seed": p.seed}
        if beta_set:
            kw["beta"] = beta
        if eps_set:
            kw["eps1"] = eps1
        return opt_mod.make_for_point(p.algo, alpha, m, **kw)
    base = _base_optimizer(base_cfg, m)
    # reuse the template's transport when it already is the point's kind —
    # this is what lets a task-scaled instance (e.g. TopKTransport(k=...))
    # survive the sweep instead of being clobbered by kind defaults
    if getattr(base.transport, "mode", None) == p.quantize:
        transport = base.transport
    else:
        transport = _transport(p.quantize)
    o = dataclasses.replace(base, num_workers=m, transport=transport)
    return o.with_hparams(alpha=alpha, beta=beta, eps1=eps1)


def run_sweep(grid: Union[ConfigGrid, Sequence[GridPoint]],
              task: Optional[FedTask] = None, *,
              num_iters: int,
              task_factory: Optional[TaskFactory] = None,
              base_cfg=None,
              vectorize: bool = False,
              collect_metrics: bool = False) -> "SweepResult":
    """Run every grid point as (a few) single compiled device programs.

    Args:
      grid: a ``ConfigGrid`` or an explicit sequence of ``GridPoint``s
        (e.g. the four gd/hb/lag/chb baselines, which are not a cartesian
        product).
      task: the shared ``FedTask`` when the grid has a single seed.
      num_iters: scan length K for every point.
      task_factory: ``(seed, num_workers) -> FedTask``; required when the
        grid sweeps seeds or worker counts beyond the shared task.
      base_cfg: template for composition choices outside the grid axes —
        a ``repro.opt.ComposedOptimizer`` (or legacy ``FedOptConfig``)
        whose granularity / bank_dtype / censor family (e.g. adaptive) are
        kept; its alpha/beta/eps1/num_workers/quantize are overridden per
        point. Ignored by named-``algo`` points, which build through the
        registry.
      vectorize: ``False`` (default) = ``lax.map``, bit-exact vs
        ``simulator.run``; ``True`` = ``vmap``, faster on large grids but
        ulp-divergent (see module docstring).
      collect_metrics: thread a per-round ``repro.obs`` MetricBag through
        every point's trajectory (``History.metrics`` becomes a
        ``{name: (K,) array}`` series). Static per partition — it changes
        the mapped program's outputs but not its partition key, and adds
        zero extra compiles relative to a metrics-off sweep of the same
        grid (pinned by tests/test_obs.py via ``obs.compile_log``).
    Returns:
      A ``SweepResult`` with one full ``History`` per point, in grid order.
    """
    if task is None and task_factory is None:
        raise ValueError("need a task or a task_factory")
    m_default = _leading_dim(task) if task is not None else None
    if base_cfg is not None and m_default is None:
        m_default = base_cfg.num_workers
    points = grid.points(m_default) if isinstance(grid, ConfigGrid) \
        else tuple(grid)
    if not points:
        raise ValueError("empty grid")

    granularity = "global" if base_cfg is None else \
        getattr(as_optimizer(base_cfg), "granularity", "global")

    if base_cfg is not None:
        # a censor without an eps1 hook (adaptive/stochastic/custom) keeps
        # its own thresholds (see with_hparams), so a varying eps axis
        # would produce N identical trajectories labeled as distinct
        # points — refuse loudly rather than plot a flat "frontier"
        base_censor = getattr(as_optimizer(base_cfg), "censor", None)
        if base_censor is not None and \
                not isinstance(base_censor, (Eq8Censor, NeverCensor)):
            eps_axis = {p.eps1 for p in points if p.algo is None}
            if len(eps_axis) > 1:
                raise ValueError(
                    f"base_cfg censor {type(base_censor).__name__} has no "
                    "eps1 hook, so the grid's varying eps1 axis "
                    f"({sorted(eps_axis)[:4]}...) would be silently "
                    "ignored; sweep its own threshold via named "
                    "GridPoint(algo=...) points instead")

    # ---- partition by the static axes (worker count, quantize, seed,
    # named algorithm; plus eps1 under per_tensor granularity, whose byte
    # accounting needs a static threshold) ----
    groups: dict[tuple, list[int]] = {}
    for i, p in enumerate(points):
        m = p.num_workers if p.num_workers is not None else m_default
        if m is None:
            raise ValueError(
                f"point {i} has no num_workers and no task to infer it from")
        eps_static = p.eps1 if (granularity == "per_tensor"
                                and p.algo is None) else None
        # named points additionally partition by which optional axes they
        # set (see _named_axes): set vs builder-default axes trace
        # different scalars, i.e. different compiled programs
        axes = _named_axes(p) if p.algo is not None else None
        groups.setdefault((m, p.quantize, p.seed, p.algo, eps_static, axes),
                          []).append(i)

    if task_factory is None and any(k[2] != 0 for k in groups):
        # a shared task has no seed axis: silently running it under a
        # non-default seed label would mislabel every result row
        raise ValueError(
            "non-default seeds need a task_factory(seed, num_workers)")

    histories: list[Optional[History]] = [None] * len(points)
    specs: list[Optional[dict]] = [None] * len(points)
    elapsed = 0.0
    for (m, quant, seed, algo, eps_static, axes), idxs in groups.items():
        if task_factory is not None:
            group_task = task_factory(seed, m)
        else:
            group_task = task
        if group_task is None or _leading_dim(group_task) != m:
            raise ValueError(
                f"group needs a task with num_workers={m}; pass a "
                "task_factory to sweep worker counts")
        for i in idxs:     # full composition of each point, host-side
            try:
                specs[i] = opt_mod.to_spec(
                    _point_optimizer(points[i], m, base_cfg))
            except ValueError:
                # a custom stage class outside the spec vocabulary (see
                # opt.CENSOR_KINDS etc.) is still perfectly sweepable —
                # record no spec rather than refusing to run the grid
                specs[i] = None
        t0 = time.perf_counter()
        group_hist = _run_group([points[i] for i in idxs], m, base_cfg,
                                eps_static, group_task, num_iters,
                                vectorize, collect_metrics)
        elapsed += time.perf_counter() - t0
        for j, i in enumerate(idxs):
            histories[i] = jax.tree_util.tree_map(
                lambda x, j=j: x[j], group_hist)
    return SweepResult(points=points, num_iters=num_iters,
                       histories=tuple(histories), elapsed_s=elapsed,
                       num_programs=len(groups), specs=tuple(specs))


@draw_exact
def _run_group(pts: list[GridPoint], m: int, base_cfg,
               eps_static: Optional[float], task: FedTask,
               num_iters: int, vectorize: bool,
               collect_metrics: bool = False) -> History:
    """Compile and execute one static partition; returns a stacked History.

    The task is closed over (program constants), matching ``simulator.run``
    bit-for-bit; only (alpha, beta, eps1) live in device arrays. Every
    point of the partition shares its quantize/seed/algo statics, so the
    representative ``pts[0]`` decides them.
    """
    from ..obs import compile_log
    compile_log.record("sweep", "partition")   # trace-time tick per program
    rep = pts[0]
    ftype = _float_dtype()
    pts_dev = (jnp.asarray([p.alpha for p in pts], ftype),
               jnp.asarray([p.beta for p in pts], ftype),
               jnp.asarray([p.eps1 for p in pts], ftype))

    def one_point(point):
        alpha, beta, eps1 = point
        if eps_static is not None:      # per_tensor: eps1 closed over
            eps1 = eps_static
        o = _point_optimizer(rep, m, base_cfg,
                             alpha=alpha, beta=beta, eps1=eps1)
        return simulator.trajectory(o, task, num_iters,
                                    collect_metrics=collect_metrics)

    # pts_dev is built fresh per partition and never reused after the
    # call, so its buffers are donated to the compiled program (the
    # hyperparameter vectors are tiny, but donation also documents the
    # ownership handoff the fused step's carry donation relies on).
    # No History output is (P,)-shaped, so XLA cannot actually reuse
    # these buffers — suppress its (expected) "not usable" warning.
    if vectorize:
        # repro-lint: disable=vmap-in-draw-exact -- vectorize=True is the
        # documented opt-in fast path; callers accept ulp-level drift vs
        # the default lax.map program (test_sweep_vectorized_mode_close)
        program = jax.jit(jax.vmap(one_point), donate_argnums=(0,))
    else:
        program = jax.jit(lambda xs: jax.lax.map(one_point, xs),
                          donate_argnums=(0,))
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        out = program(pts_dev)
    jax.block_until_ready(out.objective)
    return jax.tree_util.tree_map(np.asarray, out)


# ---------------------------------------------------------------- results
@dataclasses.dataclass(frozen=True)
class SweepResult:
    """Stacked trajectories + accounting for every grid point, in order.

    Attributes:
      points: the concrete grid points, index-aligned with ``histories``.
      num_iters: K, shared by all points.
      histories: one host-side (numpy-leaved) ``History`` per point.
      elapsed_s: wall-clock seconds for all device programs (compile+run).
      num_programs: how many static partitions were compiled.
      specs: the full ``repro.opt`` registry spec of each point's
        optimizer (``opt.from_spec(specs[i])`` rebuilds it exactly), so an
        exported artifact is reproducible without the code that made it.
        ``None`` for points whose composition uses a custom stage class
        not registered in the spec vocabulary (``opt.CENSOR_KINDS`` &co).
    """
    points: tuple[GridPoint, ...]
    num_iters: int
    histories: tuple[History, ...]
    elapsed_s: float
    num_programs: int
    specs: tuple[dict, ...] = ()

    def __len__(self) -> int:
        return len(self.points)

    def history(self, i: int) -> History:
        """The full per-point ``History`` (same layout as simulator.run)."""
        return self.histories[i]

    # ------------------------------------------------------ stacked views
    @property
    def objective(self) -> np.ndarray:
        """(B, K) objective trajectories."""
        return np.stack([np.asarray(h.objective) for h in self.histories])

    @property
    def comm_cum(self) -> np.ndarray:
        """(B, K) cumulative uplink transmissions."""
        return np.stack([np.asarray(h.comm_cum) for h in self.histories])

    @property
    def agg_grad_sqnorm(self) -> np.ndarray:
        """(B, K) ||grad_k||^2 trajectories."""
        return np.stack([np.asarray(h.agg_grad_sqnorm)
                         for h in self.histories])

    @property
    def uplink_bytes(self) -> np.ndarray:
        """(B,) exact cumulative uplink payload bytes per point."""
        return np.asarray([h.final_state.comm.uplink_bytes_exact()
                           for h in self.histories], np.int64)

    def metrics(self, i: int) -> dict:
        """Point ``i``'s stacked ``{name: (K,) array}`` MetricBag series.

        Empty unless the sweep ran with ``collect_metrics=True``.
        """
        bags = self.histories[i].metrics
        return dict(bags) if bags else {}

    def metrics_summary(self) -> list[dict]:
        """One ``{name: final float}`` row per point (JSON-ready).

        Final-round values: cumulative series (bytes, counts) read their
        total; rate-like series read the last round. Empty dicts when the
        sweep did not collect metrics.
        """
        from ..obs.metrics import summarize
        return [summarize(self.metrics(i)) if self.metrics(i) else {}
                for i in range(len(self.points))]

    def _fstar_for(self, fstar, i: int) -> float:
        if isinstance(fstar, dict):
            return float(fstar[self.points[i].seed])
        if np.ndim(fstar) == 0:
            return float(fstar)
        return float(fstar[i])

    def frontier(self, fstar, tol: float) -> list[dict]:
        """Per-point communication/accuracy frontier rows.

        Args:
          fstar: optimal value — a scalar, a per-point sequence, or a
            ``{seed: fstar}`` dict for multi-seed sweeps.
          tol: target objective error (paper-style ``f - f* < tol``).
        Returns:
          One dict per point: the point's coordinates plus
          ``iters_to_tol``/``comms_to_tol`` (-1 = never reached),
          ``total_comms``, ``final_err``, and exact ``uplink_bytes``.
        """
        rows = []
        ub = self.uplink_bytes          # (B,) once, not once per row
        for i, (p, h) in enumerate(zip(self.points, self.histories)):
            fs = self._fstar_for(fstar, i)
            rows.append({
                "index": i,
                "algo": p.algo_name,
                "alpha": p.alpha, "beta": p.beta, "eps1": p.eps1,
                "seed": p.seed, "quantize": p.quantize,
                "num_workers": int(np.asarray(h.mask).shape[1]),
                "iters_to_tol": simulator.iterations_to_accuracy(h, fs, tol),
                "comms_to_tol": simulator.comms_to_accuracy(h, fs, tol),
                "total_comms": int(np.asarray(h.comm_cum)[-1]),
                "final_err": float(np.asarray(h.objective)[-1]) - fs,
                "uplink_bytes": int(ub[i]),
            })
        return rows

    # ----------------------------------------------------------- export
    def to_json(self, path: Optional[str] = None,
                include_trajectories: bool = True,
                fstar=None, tol: Optional[float] = None) -> str:
        """Serialize the sweep for BENCH artifacts.

        Args:
          path: if given, also write the JSON there.
          include_trajectories: include (B, K) objective/comm trajectories
            (masks are always omitted — they dominate the payload).
          fstar, tol: if both given, a ``frontier`` section is included.
        Returns:
          The JSON string.
        """
        doc: dict[str, Any] = {
            "num_points": len(self.points),
            "num_iters": self.num_iters,
            "num_programs": self.num_programs,
            "elapsed_s": self.elapsed_s,
            "points": [p._asdict() for p in self.points],
            "specs": list(self.specs),
            "uplink_bytes": self.uplink_bytes.tolist(),
        }
        if include_trajectories:
            doc["objective"] = self.objective.tolist()
            doc["comm_cum"] = self.comm_cum.tolist()
        summary = self.metrics_summary()
        if any(summary):
            doc["metrics"] = summary
        if fstar is not None and tol is not None:
            doc["frontier"] = self.frontier(fstar, tol)
        text = json.dumps(doc, indent=1, sort_keys=True)
        if path is not None:
            with open(path, "w") as f:
                f.write(text)
        return text

    def to_csv(self, fstar, tol: float, path: Optional[str] = None) -> str:
        """Frontier rows as CSV (header + one line per point)."""
        rows = self.frontier(fstar, tol)
        cols = ["index", "algo", "alpha", "beta", "eps1", "seed", "quantize",
                "num_workers", "iters_to_tol", "comms_to_tol", "total_comms",
                "final_err", "uplink_bytes"]
        lines = [",".join(cols)]
        for r in rows:
            lines.append(",".join(
                "" if r[c] is None else f"{r[c]:.6e}" if c == "final_err"
                else str(r[c]) for c in cols))
        text = "\n".join(lines) + "\n"
        if path is not None:
            with open(path, "w") as f:
                f.write(text)
        return text
