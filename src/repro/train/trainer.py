"""CHB training loop at LLM scale.

Composes: model zoo (repro.models) + CHB optimizer family (repro.core) +
sharded data pipeline (repro.data.lm_data) + checkpointing. Algorithm
selectable per paper Sec. IV: gd | hb | lag | chb (+ optional int8 deltas).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import opt
from ..configs.base import ModelConfig
from ..core import distributed
from ..core.chb import FedOptConfig
from ..checkpoint import checkpoint as ckpt
from ..data import lm_data
from ..launch import sharding as shr
from ..launch.mesh import dp_axes
from ..models import model
from ..obs import profile


@dataclasses.dataclass
class TrainConfig:
    algorithm: str = "chb"           # gd | hb | lag | chb
    strategy: str = "scan"           # scan | pod
    num_workers: int = 4
    alpha: float = 3e-2
    beta: float = 0.4
    eps1_scale: float = 0.1
    quantize: Optional[str] = None
    global_batch: int = 16
    seq_len: int = 256
    steps: int = 100
    log_every: int = 10
    ckpt_every: int = 0
    ckpt_path: str = "checkpoints/run"
    seed: int = 0
    remat: str = "none"
    moe_mode: str = "scan"
    # donate (params, state) into the jitted step so XLA reuses their
    # buffers across iterations (halves the parameter-state footprint).
    # Safe by construction: distributed.init_scan_state /
    # FedOptimizer.init copy prev_params up front, so the step never
    # reads a buffer it also overwrites. Set False to keep pre-step
    # (params, state) values alive for debugging.
    donate: bool = True


def _worker_count(tc: TrainConfig, mesh=None) -> int:
    return mesh.shape["pod"] if (tc.strategy == "pod" and mesh is not None) \
        else tc.num_workers


def make_optimizer(tc: TrainConfig, mesh=None) -> opt.ComposedOptimizer:
    """Resolve ``tc.algorithm`` through the ``repro.opt`` registry.

    Any registered name is accepted, but the distributed execution
    strategies (``core/distributed``) only realize eq.-(8)/uncensored
    policies with dense or int8 transport — anything else raises here
    rather than silently running uncensored.
    """
    m = _worker_count(tc, mesh)
    kw = {"quantize": tc.quantize}
    if tc.algorithm == "hb":
        kw["beta"] = tc.beta
    if tc.algorithm in ("lag", "chb"):
        kw["eps1_scale"] = tc.eps1_scale
    o = opt.make(tc.algorithm, tc.alpha, m, **kw)
    if not isinstance(o.censor, (opt.NeverCensor, opt.Eq8Censor)):
        raise NotImplementedError(
            f"algorithm {tc.algorithm!r} uses censor policy "
            f"{type(o.censor).__name__}, which the scan/pod training "
            "strategies do not realize (eq.-8 / uncensored only)")
    return o


def make_fed_config(tc: TrainConfig, mesh=None) -> FedOptConfig:
    """DEPRECATED: the legacy-config view of ``make_optimizer``."""
    o = make_optimizer(tc, mesh)
    return FedOptConfig(alpha=o.alpha, num_workers=o.num_workers,
                        beta=o.beta, eps1=o.eps1, quantize=o.quantize)


def train(cfg: ModelConfig, tc: TrainConfig, mesh=None, verbose=True):
    """Returns (params, state, history list of metric dicts)."""
    with profile.annotate("train"):
        with profile.annotate("train/setup"):
            fcfg = make_optimizer(tc, mesh)
            m = fcfg.num_workers

            act = None
            if mesh is not None:
                # inside the pod-manual region only auto axes may appear in
                # sharding constraints
                axes = ("data",) if tc.strategy == "pod" else dp_axes(mesh)
                act = NamedSharding(mesh, P(axes))

            def loss_fn(params, batch):
                return model.train_loss(params, cfg, batch,
                                        moe_mode=tc.moe_mode,
                                        remat=tc.remat, act_spec=act)[0]

            params = model.init_params(jax.random.PRNGKey(tc.seed), cfg)
            if mesh is not None:
                shardings = shr.params_shardings(
                    jax.eval_shape(lambda: params), mesh,
                    fsdp_axes=dp_axes(mesh) if tc.strategy == "scan"
                    else ("data",),
                    gather_safe=(tc.strategy == "pod"))
                params = jax.tree_util.tree_map(jax.device_put, params,
                                                shardings)

            if tc.strategy == "pod":
                assert mesh is not None and "pod" in mesh.axis_names
                state = distributed.init_pod_state(fcfg, params, mesh)
                step_fn = distributed.make_pod_step(fcfg, loss_fn, mesh)
                workers_for_data = None
            else:
                state = distributed.init_scan_state(fcfg, params)
                step_fn = distributed.make_scan_step(fcfg, loss_fn)
                workers_for_data = m

            step_fn = jax.jit(step_fn,
                              donate_argnums=(0, 1) if tc.donate else ())
            data = lm_data.batch_iterator(cfg, global_batch=tc.global_batch,
                                          seq_len=tc.seq_len,
                                          num_workers=workers_for_data,
                                          seed=tc.seed)
        history = []
        t0 = time.time()
        for step in range(tc.steps):
            with profile.step("train/step", step):
                with profile.annotate("train/data"):
                    batch = next(data)
                with profile.annotate("train/dispatch"):
                    params, state, metrics = step_fn(params, state, batch)
                if step % tc.log_every == 0 or step == tc.steps - 1:
                    with profile.annotate("train/log"):
                        rec = {k: float(v) for k, v in metrics.items()}
                        rec.update(step=step,
                                   comms=int(state.comm.total_uplinks),
                                   comm_savings=float(
                                       state.comm.savings_vs_dense()),
                                   wall_s=round(time.time() - t0, 1))
                        history.append(rec)
                        if verbose:
                            print(f"step {step:5d} loss={rec['loss']:.4f} "
                                  f"tx={rec['transmitted']:.0f}/{m} "
                                  f"comms={rec['comms']} "
                                  f"saved={rec['comm_savings']*100:.1f}%")
                if tc.ckpt_every and step and step % tc.ckpt_every == 0:
                    ckpt.save(f"{tc.ckpt_path}_step{step}",
                              {"params": params},
                              metadata={"step": step, "arch": cfg.name})
        return params, state, history
