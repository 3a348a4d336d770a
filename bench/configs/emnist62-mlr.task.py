"""The emnist62-mlr population: data made on the device from the seed, and
the ``FedTask`` that ``repro.fed.run_mesh`` drives.

Each writer's samples are made from keys folded by the writer's absolute
id, so the population is the same however it is split over devices, and a
reference can remake any writer alone (``client_data``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from scipy.special import ndtri

# writers made per vmapped chunk of the generator (170 divides 3,400 and 850)
GEN_CHUNK = 170


def sample_counts(cfg: dict, seed: int) -> np.ndarray:
    """Per-writer sample counts: one fixed multiset, in a seeded order."""
    m, total, cap = cfg["clients"], cfg["train_images"], cfg["max_samples"]
    z = ndtri((np.arange(m) + 0.5) / m)
    raw = np.exp(cfg["count_sigma"] * z)
    counts = np.minimum(np.floor(raw * total / raw.sum()), cap).astype(np.int64)
    # hand the rounding remainder to the writers below the cap, largest first
    order = np.argsort(-raw, kind="stable")
    short = total - int(counts.sum())
    for i in order:
        if short == 0:
            break
        add = min(short, cap - int(counts[i]))
        counts[i] += add
        short -= add
    if short or counts.sum() != total:
        raise ValueError("counts do not reach train_images under the cap")
    rng = np.random.default_rng(seed)
    return counts[rng.permutation(m)].astype(np.int32)


def _keys(seed: int):
    root = jax.random.PRNGKey(seed)
    return jax.random.split(root, 2)        # prototypes, writers


def prototypes(cfg: dict, seed: int) -> jax.Array:
    k_proto, _ = _keys(seed)
    p = cfg["prototype_mean"] + cfg["prototype_std"] * jax.random.normal(
        k_proto, (cfg["classes"], cfg["image_pixels"]), jnp.float32)
    return jnp.clip(p, 0.0, 1.0)


def client_data(cfg: dict, protos, writers_key, cid, count):
    """One writer's padded images, labels and count (traceable)."""
    n, c, d = cfg["max_samples"], cfg["classes"], cfg["image_pixels"]
    k = jax.random.fold_in(writers_key, cid)
    k_mix, k_lab, k_pix = jax.random.split(k, 3)
    mix = jax.random.dirichlet(k_mix, jnp.full((c,), cfg["label_alpha"],
                                               jnp.float32))
    y = jax.random.categorical(k_lab, jnp.log(mix + 1e-30), shape=(n,))
    valid = jnp.arange(n) < count
    x = protos[y] + cfg["pixel_noise"] * jax.random.normal(
        k_pix, (n, d), jnp.float32)
    x = jnp.where(valid[:, None], jnp.clip(x, 0.0, 1.0), 0.0)
    return {"x": x, "y": jnp.where(valid, y, 0).astype(jnp.int32),
            "n": count.astype(jnp.int32)}


def make_block(cfg: dict, seed: int, first: int, size: int, counts,
               device=None) -> dict:
    """Writers ``[first, first + size)`` in one jitted call on ``device``."""
    chunk = math.gcd(size, GEN_CHUNK)
    protos = prototypes(cfg, seed)
    _, writers_key = _keys(seed)
    ids = jnp.arange(first, first + size, dtype=jnp.uint32)
    cnt = jnp.asarray(counts[first:first + size], jnp.int32)
    if device is not None:
        protos, writers_key, ids, cnt = jax.device_put(
            (protos, writers_key, ids, cnt), device)

    @jax.jit
    def gen(protos, writers_key, ids, cnt):
        def one_chunk(args):
            i, c = args
            return jax.vmap(lambda a, b: client_data(
                cfg, protos, writers_key, a, b))(i, c)
        out = jax.lax.map(one_chunk, (ids.reshape(-1, chunk),
                                      cnt.reshape(-1, chunk)))
        return jax.tree_util.tree_map(
            lambda v: v.reshape((size,) + v.shape[2:]), out)

    return gen(protos, writers_key, ids, cnt)


def make_population(cfg: dict, seed: int, devices) -> tuple[dict, np.ndarray]:
    """The whole population, split in equal writer blocks over ``devices``
    (one block per device, in order) and joined into global arrays."""
    counts = sample_counts(cfg, seed)
    m, k = cfg["clients"], len(devices)
    if m % k:
        raise ValueError(f"{m} writers do not split over {k} devices")
    size = m // k
    blocks = [make_block(cfg, seed, i * size, size, counts, dev)
              for i, dev in enumerate(devices)]
    if k == 1:
        return blocks[0], counts
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.asarray(devices), ("clients",))

    def join(*leaves):
        shape = (m,) + leaves[0].shape[1:]
        return jax.make_array_from_single_device_arrays(
            shape, NamedSharding(mesh, P("clients")), list(leaves))
    return jax.tree_util.tree_map(join, *blocks), counts


def init_params(cfg: dict) -> dict:
    return {"W": jnp.zeros((cfg["image_pixels"], cfg["classes"]), jnp.float32),
            "b": jnp.zeros((cfg["classes"],), jnp.float32)}


def make_task(cfg: dict, data: dict):
    """The ``FedTask``: closed-form gradient of f_m, and f_m itself."""
    from repro.core.simulator import FedTask

    scale = 1.0 / cfg["train_images"]

    def _logits(params, data_m):
        return data_m["x"] @ params["W"] + params["b"]

    def _valid(data_m):
        return (jnp.arange(data_m["y"].shape[0]) < data_m["n"]).astype(
            jnp.float32)

    def grad_fn(params, data_m):
        p = jax.nn.softmax(_logits(params, data_m), axis=-1)
        onehot = jax.nn.one_hot(data_m["y"], p.shape[-1], dtype=p.dtype)
        r = (p - onehot) * (_valid(data_m) * scale)[:, None]
        return {"W": data_m["x"].T @ r, "b": jnp.sum(r, axis=0)}

    def loss_fn(params, data_m):
        z = _logits(params, data_m)
        # the gold logit by a one-hot product: a gather of one lane per
        # sample lowers to a slow TPU gather over the padded samples
        onehot = jax.nn.one_hot(data_m["y"], z.shape[-1], dtype=z.dtype)
        per = jax.nn.logsumexp(z, axis=-1) - jnp.sum(z * onehot, axis=-1)
        return jnp.sum(per * _valid(data_m)) * scale

    return FedTask(init_params=init_params(cfg), grad_fn=grad_fn,
                   loss_fn=loss_fn, worker_data=data, name=cfg["name"])
