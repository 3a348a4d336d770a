"""Plain reference of a synchronous CHB round over the emnist62-mlr population.

Written from the algorithm, not from the program: autodiff gradients of each
writer's f_m, the eq.-(8) test ``||g_m - ghat_m||^2 > eps1 ||theta^k -
theta^{k-1}||^2``, participation and uplink-loss draws keyed by (seed, round,
writer id), the stale-gradient bank, the quorum on arrived uplinks, and the
heavy-ball update (paper eq. 4). Matrix products run at ``highest``
precision; ``dtype=bfloat16`` gives the lower-precision control.

``run`` follows the program's own attempted-uplink decisions where they are
given (``forced``) and records, for every writer where they differ from its
own, how far its eq.-(8) ratio lay from the threshold. So a decision that
rounding can flip is measured, not fatal, and the trajectories stay aligned.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


# the flip margin of a decision that no rounding can flip
NO_ROUNDING = 1e30


def _loss(params, x, y, n, scale, dtype):
    z = x @ params["W"] + params["b"]
    zf = z.astype(jnp.float32) if dtype == jnp.float32 else z
    per = jax.nn.logsumexp(zf, axis=-1) - jnp.take_along_axis(
        zf, y[:, None], axis=-1)[:, 0]
    valid = (jnp.arange(y.shape[0]) < n).astype(per.dtype)
    return jnp.sum(per * valid) * jnp.asarray(scale, per.dtype)


def _draws(seed: int, round_idx: int, ids):
    rkey = jax.random.fold_in(jax.random.PRNGKey(seed), jnp.int32(round_idx))

    def one(cid):
        ck = jax.random.fold_in(rkey, cid)
        return (jax.random.uniform(jax.random.fold_in(ck, 0)),
                jax.random.uniform(jax.random.fold_in(ck, 1)))
    return jax.vmap(one)(ids)


def _sq(tree):
    return sum(jnp.sum(jnp.square(v.astype(jnp.float32)))
               for v in jax.tree_util.tree_leaves(tree))


def run(cfg: dict, data: dict, *, rounds: int, participation: float,
        loss_prob: float, quorum: float, seed: int, forced=None,
        dtype=jnp.float32, chunk: int = 170) -> dict:
    """The first ``rounds`` rounds from theta^0 = 0.

    Returns per-round ``objective``, ``participated``,
    ``attempted``, ``delivered``, ``quorum_met``, the attempted rows
    (``mask``), and ``flip_margin``: the largest |ratio - 1| over writers
    where ``forced`` overrode this reference's own decision (0 if none).
    """
    m = cfg["clients"]
    scale = 1.0 / cfg["train_images"]
    alpha, beta = cfg["alpha"], cfg["beta"]
    eps1 = cfg["eps1_scale"] / (alpha ** 2 * m ** 2)
    chunk = math.gcd(m, chunk)
    sync = participation >= 1.0 and loss_prob == 0.0
    ids = jnp.arange(m, dtype=jnp.uint32)

    x = data["x"].astype(dtype)
    y, n = data["y"], data["n"]
    params = {"W": jnp.zeros((cfg["image_pixels"], cfg["classes"]), dtype),
              "b": jnp.zeros((cfg["classes"],), dtype)}

    @jax.jit
    def client_pass(params, x, y, n):
        def one_chunk(args):
            xc, yc, nc = args

            def one(xm, ym, nm):
                return jax.value_and_grad(_loss)(params, xm, ym, nm, scale,
                                                 dtype)
            return jax.vmap(one)(xc, yc, nc)
        with jax.default_matmul_precision("highest"):
            losses, grads = jax.lax.map(one_chunk, (
                x.reshape((-1, chunk) + x.shape[1:]),
                y.reshape((-1, chunk) + y.shape[1:]),
                n.reshape(-1, chunk)))
        flat = lambda v: v.reshape((m,) + v.shape[2:])  # noqa: E731
        return flat(losses), jax.tree_util.tree_map(flat, grads)

    bank = jax.tree_util.tree_map(
        lambda p: jnp.zeros((m,) + p.shape, p.dtype), params)
    prev = params
    out = {k: [] for k in ("objective", "participated", "attempted",
                           "delivered", "quorum_met", "mask")}
    flip_margin = 0.0
    for k in range(rounds):
        losses, grads = client_pass(params, x, y, n)
        dsq = np.asarray(jax.vmap(_sq)(jax.tree_util.tree_map(
            lambda g, h: g - h, grads, bank)), np.float64)
        ssq = float(_sq(jax.tree_util.tree_map(lambda a, b: a - b, params,
                                               prev)))
        thr = eps1 * ssq
        own = dsq > thr
        if sync:
            part = np.ones(m, bool)
            chan = np.ones(m, bool)
        else:
            u_part, u_drop = (np.asarray(u) for u in _draws(seed, k, ids))
            part = u_part < participation
            chan = u_drop >= loss_prob
        att = own & part
        if forced is not None:
            f = np.asarray(forced[k]).astype(bool)
            differ = f != att
            if np.any(differ & ~part) or (np.any(differ) and thr == 0.0):
                # a non-participant uploaded, or a flip against a strict
                # zero threshold: no rounding explains either
                flip_margin = max(flip_margin, NO_ROUNDING)
            elif np.any(differ):
                ratio = dsq[differ] / thr
                flip_margin = max(flip_margin,
                                  float(np.max(np.abs(ratio - 1.0))))
            att = f
        dlv = att & chan
        sel = jnp.asarray(dlv, dtype)
        bank = jax.tree_util.tree_map(
            lambda h, g: h + sel.reshape((m,) + (1,) * (h.ndim - 1))
            * (g - h), bank, grads)
        agg = jax.tree_util.tree_map(lambda h: jnp.sum(h, axis=0), bank)
        n_part, n_att, n_del = int(part.sum()), int(att.sum()), int(dlv.sum())
        arrived = n_part - (n_att - n_del)
        met = arrived >= math.ceil(quorum * n_part) and n_part > 0
        out["objective"].append(float(jnp.sum(losses.astype(jnp.float32))))
        out["participated"].append(n_part)
        out["attempted"].append(n_att)
        out["delivered"].append(n_del)
        out["quorum_met"].append(bool(met))
        out["mask"].append(att.astype(np.int8))
        if met:
            with jax.default_matmul_precision("highest"):
                new = jax.tree_util.tree_map(
                    lambda t, a, tp: t - jnp.asarray(alpha, dtype) * a
                    + jnp.asarray(beta, dtype) * (t - tp), params, agg, prev)
            prev, params = params, new
    res = {k: np.asarray(v) for k, v in out.items()}
    res["flip_margin"] = flip_margin
    res["payload_bytes"] = 4 * (cfg["image_pixels"] * cfg["classes"]
                                + cfg["classes"])
    return res
