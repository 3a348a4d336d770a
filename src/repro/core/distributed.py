"""CHB at datacenter scale: the two execution strategies (DESIGN.md §3).

scan strategy (pure pjit, any mesh)
-----------------------------------
Federated workers are M logical batch groups. A lax.scan iterates workers;
each iteration computes that worker's gradient on the FULL mesh (params stay
FSDP+TP sharded by auto-SPMD), applies the eq.-(8) censor test, and folds the
(masked) delta into the running aggregate. The stale-gradient bank ghat is a
leading-M stacked pytree, FSDP-sharded like the params, so the extra state is
M*P/num_devices bytes per device.

pod strategy (shard_map manual over "pod")
------------------------------------------
Federated workers ARE pods. Everything inside a pod (data/model axes) stays
auto-SPMD; only the pod axis is manual. Per-pod gradients never cross the pod
boundary unless the censor test fires: the ONLY cross-pod collective is
`psum(masked delta, "pod")` — exactly eq. (5). The server aggregate `nabla`
is carried explicitly (replicated across pods), so this strategy implements
the paper's recursion literally, and the collective roofline term shrinks to
the censored-delta traffic (int8 if quantization is on).
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .accounting import CommStats
# cfg arguments below accept either a legacy FedOptConfig or a repro.opt
# ComposedOptimizer: both expose the flat hyperparameter views
# (alpha/beta/eps1/quantize/num_workers/bank_dtype) these strategies read.
from .quantize import payload_bytes_dense, payload_bytes_int8, \
    quantize_roundtrip
from .util import tree_sqnorm


class DistFedState(NamedTuple):
    prev_params: Any
    ghat: Any          # scan: (M, ...) stacked; pod: per-pod (leading 1 inside)
    nabla: Any         # pod strategy only: eq.(5) server aggregate (else ())
    err: Any           # quantization error feedback (or ())
    comm: CommStats
    step: jax.Array


def _tree_cast_like(t, ref):
    return jax.tree_util.tree_map(lambda x, r: x.astype(r.dtype), t, ref)


def _check_realizable(cfg) -> None:
    """The scan/pod strategies realize censoring as ``dsq > eps1 * ssq``
    only. A composed optimizer with any other censor policy (adaptive,
    stochastic, custom) would silently run uncensored through the flat
    ``cfg.eps1`` view — refuse it loudly instead."""
    censor = getattr(cfg, "censor", None)
    if censor is None:
        return      # legacy FedOptConfig: eq-8 semantics by construction
    from ..opt.censor import Eq8Censor, NeverCensor
    if not isinstance(censor, (Eq8Censor, NeverCensor)):
        raise NotImplementedError(
            f"censor policy {type(censor).__name__} is not realizable by "
            "the scan/pod training strategies (eq.-8 / uncensored only); "
            "run it through core.simulator or repro.fed instead")


def _payload_bytes(cfg, params) -> int:
    # must stay a Python int: CommStats.update only takes the exact
    # split-counter path for ints (see accounting.py)
    if cfg.quantize == "int8":
        return payload_bytes_int8(params)
    return payload_bytes_dense(params)


def make_client_fold(mesh, axis: str = "clients"):
    """Build the server-side quorum fold for a client mesh.

    Takes a pytree whose leaves are ``(K, ...)`` stacks of per-shard
    partial sums (one row per device on ``axis``, assembled with
    ``launch.sharding.stack_shards``) and returns the replicated total:
    each shard contributes its own row and a single ``psum`` over ``axis``
    folds them — the ONLY cross-shard collective in the sharded federated
    runtime, so it is what ``obs.hlo_report`` surfaces as the fold cost.

    The fold is a fixed-order K-term tree reduction, identical for every
    output element, which is what makes the K-invariance anchors in
    docs/fed_scaling.md hold to ulp-level (and bitwise at K=1, where the
    psum is the identity).
    """
    from jax.sharding import PartitionSpec as _P

    def fold_local(stacked):
        return jax.tree_util.tree_map(
            lambda v: jax.lax.psum(v[0], axis), stacked)

    # check_vma=False: the out_specs still enforce the layout
    return jax.shard_map(fold_local, mesh=mesh, in_specs=(_P(axis),),
                         out_specs=_P(), axis_names={axis}, check_vma=False)


# ============================================================ scan strategy
def init_scan_state(cfg, params) -> DistFedState:
    bank_dt = cfg.bank_dtype
    bank = jax.tree_util.tree_map(
        lambda x: jnp.zeros((cfg.num_workers,) + x.shape,
                            bank_dt or x.dtype), params)
    err = jax.tree_util.tree_map(jnp.zeros_like, bank) if cfg.quantize else ()
    # copy: prev_params must not alias params (donation safety at step 0)
    prev = jax.tree_util.tree_map(jnp.copy, params)
    return DistFedState(prev_params=prev, ghat=bank, nabla=(), err=err,
                        comm=CommStats.init(cfg.num_workers),
                        step=jnp.zeros((), jnp.int32))


def make_scan_step(cfg,
                   loss_fn: Callable[[Any, Any], jax.Array]):
    """Build train_step(params, state, batch) for the scan strategy.

    loss_fn(params, worker_batch) -> scalar loss for ONE worker's chunk.
    batch: pytree with leading axis M (worker chunks).
    """
    _check_realizable(cfg)
    grad_fn = jax.value_and_grad(loss_fn)

    def train_step(params, state: DistFedState, batch):
        ssq = tree_sqnorm(jax.tree_util.tree_map(
            jnp.subtract, params, state.prev_params))

        def per_worker(carry, xs):
            agg, n_tx, loss_sum = carry
            if cfg.quantize:
                mbatch, ghat_m, err_m = xs
            else:
                mbatch, ghat_m = xs
                err_m = None
            loss, g = grad_fn(params, mbatch)
            delta = jax.tree_util.tree_map(
                lambda gg, h: gg.astype(h.dtype) - h, g, ghat_m)
            if err_m is not None:
                delta = jax.tree_util.tree_map(jnp.add, delta, err_m)
            dsq = tree_sqnorm(delta)
            send = (dsq > cfg.eps1 * ssq).astype(jnp.float32) \
                if cfg.eps1 > 0 else jnp.ones((), jnp.float32)
            if cfg.quantize == "int8":
                payload = jax.tree_util.tree_map(quantize_roundtrip, delta)
                new_err = jax.tree_util.tree_map(
                    lambda d, q, e: send * (d - q) + (1 - send) * e,
                    delta, payload, err_m)
            else:
                payload = delta
                new_err = None
            ghat_new = jax.tree_util.tree_map(
                lambda h, q: h + send * q.astype(h.dtype), ghat_m, payload)
            agg = jax.tree_util.tree_map(
                lambda a, h: a + h.astype(a.dtype), agg, ghat_new)
            out = (ghat_new, new_err, send) if cfg.quantize else \
                (ghat_new, send)
            return (agg, n_tx + send, loss_sum + loss), out

        agg0 = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, jnp.float32), params)
        xs = (batch, state.ghat, state.err) if cfg.quantize else \
            (batch, state.ghat)
        (agg, n_tx, loss_sum), outs = jax.lax.scan(
            per_worker, (agg0, jnp.zeros((), jnp.float32),
                         jnp.zeros((), jnp.float32)), xs)
        if cfg.quantize:
            new_ghat, new_err, mask = outs
        else:
            new_ghat, mask = outs
            new_err = ()

        new_params = jax.tree_util.tree_map(
            lambda t, a, tp: (t.astype(jnp.float32)
                              - cfg.alpha * a
                              + cfg.beta * (t.astype(jnp.float32)
                                            - tp.astype(jnp.float32))
                              ).astype(t.dtype),
            params, agg, state.prev_params)

        new_state = DistFedState(
            prev_params=params, ghat=new_ghat, nabla=(), err=new_err,
            comm=state.comm.update(mask, _payload_bytes(cfg, params)),
            step=state.step + 1)
        metrics = {"loss": loss_sum / cfg.num_workers, "transmitted": n_tx,
                   "step_sqnorm": ssq, "agg_grad_sqnorm": tree_sqnorm(agg)}
        return new_params, new_state, metrics

    return train_step


# ============================================================= pod strategy
def init_pod_state(cfg, params, mesh) -> DistFedState:
    """ghat/err get a leading pod axis sharded over "pod"."""
    npod = mesh.shape["pod"]
    assert cfg.num_workers == npod, (cfg.num_workers, npod)
    bank_dt = cfg.bank_dtype

    def stack(x):
        return jnp.zeros((npod,) + x.shape, bank_dt or x.dtype)

    bank = jax.tree_util.tree_map(stack, params)
    err = jax.tree_util.tree_map(stack, params) if cfg.quantize else ()
    nabla = jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, bank_dt or x.dtype), params)
    # copy: prev_params must not alias params (donation safety at step 0)
    prev = jax.tree_util.tree_map(jnp.copy, params)
    return DistFedState(prev_params=prev, ghat=bank, nabla=nabla, err=err,
                        comm=CommStats.init(npod),
                        step=jnp.zeros((), jnp.int32))


def make_pod_step(cfg,
                  loss_fn: Callable[[Any, Any], jax.Array], mesh):
    """Build train_step for the pod strategy (multi-pod mesh required).

    batch: pytree with leading batch axis sharded P("pod", "data") — each pod
    sees its own shard; censoring gates the cross-pod psum of deltas.
    """
    _check_realizable(cfg)
    grad_fn = jax.value_and_grad(loss_fn)
    npod = mesh.shape["pod"]

    def inner(params, prev_params, ghat, nabla, err, batch):
        # leading pod axis was split by shard_map -> local block of size 1
        ghat = jax.tree_util.tree_map(lambda x: x[0], ghat)
        if cfg.quantize:
            err = jax.tree_util.tree_map(lambda x: x[0], err)
        loss, g = grad_fn(params, batch)
        loss_mean = jax.lax.psum(loss, "pod") / npod
        ssq = tree_sqnorm(jax.tree_util.tree_map(
            jnp.subtract, params, prev_params))
        delta = jax.tree_util.tree_map(
            lambda gg, h: gg.astype(h.dtype) - h, g, ghat)
        if cfg.quantize:
            delta = jax.tree_util.tree_map(
                lambda d, e: d + e.astype(d.dtype), delta, err)
        dsq = tree_sqnorm(delta)
        send = (dsq > cfg.eps1 * ssq).astype(jnp.float32) \
            if cfg.eps1 > 0 else jnp.ones((), jnp.float32)
        if cfg.quantize == "int8":
            payload = jax.tree_util.tree_map(quantize_roundtrip, delta)
            new_err = jax.tree_util.tree_map(
                lambda d, q, e: (send * (d - q) + (1 - send) * e.astype(d.dtype)
                                 ).astype(e.dtype), delta, payload, err)
        else:
            payload = delta
            new_err = ()
        masked = jax.tree_util.tree_map(
            lambda q: q * send.astype(q.dtype), payload)
        # >>> THE censored cross-pod collective (eq. 5) <<<
        summed = jax.tree_util.tree_map(
            lambda x: jax.lax.psum(x, "pod"), masked)
        new_nabla = jax.tree_util.tree_map(
            lambda nb, s: nb + s.astype(nb.dtype), nabla, summed)
        new_ghat = jax.tree_util.tree_map(
            lambda h, q: h + send.astype(h.dtype) * q.astype(h.dtype),
            ghat, payload)
        new_params = jax.tree_util.tree_map(
            lambda t, nb, tp: (t.astype(jnp.float32)
                               - cfg.alpha * nb.astype(jnp.float32)
                               + cfg.beta * (t.astype(jnp.float32)
                                             - tp.astype(jnp.float32))
                               ).astype(t.dtype),
            params, new_nabla, prev_params)
        n_tx = jax.lax.psum(send, "pod")
        mask_all = jax.lax.all_gather(send, "pod")  # (npod,)
        dsq_mean = jax.lax.psum(dsq, "pod") / npod
        restack = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)
        return (new_params, new_nabla, restack(new_ghat),
                restack(new_err) if cfg.quantize else (),
                mask_all, n_tx, dsq_mean, ssq, loss_mean)

    pspec = P()  # params replicated over pod (data/model sharding is auto)
    in_specs = (pspec, pspec, P("pod"), pspec,
                P("pod") if cfg.quantize else P(), P("pod"))
    out_specs = (pspec, pspec, P("pod"),
                 P("pod") if cfg.quantize else P(), P(), P(), P(), P(), P())
    # manual over "pod" only: data/model stay auto-SPMD inside a pod
    sharded = jax.shard_map(inner, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, axis_names={"pod"},
                            check_vma=False)

    def train_step(params, state: DistFedState, batch):
        (new_params, new_nabla, new_ghat, new_err, mask, n_tx, dsq, ssq,
         loss) = sharded(params, state.prev_params, state.ghat, state.nabla,
                         state.err, batch)
        new_state = DistFedState(
            prev_params=params, ghat=new_ghat, nabla=new_nabla, err=new_err,
            comm=state.comm.update(mask, _payload_bytes(cfg, params)),
            step=state.step + 1)
        metrics = {"loss": loss, "transmitted": n_tx, "step_sqnorm": ssq,
                   "delta_sqnorm": dsq,
                   "agg_grad_sqnorm": tree_sqnorm(new_nabla)}
        return new_params, new_state, metrics

    return train_step
