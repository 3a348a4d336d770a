"""Plain reference of CHB training of the lm124m decoder.

Written from the architecture and the paper, not from the program: a
pre-norm decoder (RMSNorm, rotary positions, causal softmax attention,
SwiGLU MLP, untied output head) in straightforward ``jax.numpy``, its
weights drawn from the seed by the documented initialisation, its tokens
drawn from the seeded first-order Markov chain that the trainer's data
follows, and the CHB step of the paper (eq. 8 censoring, stale-gradient
bank, heavy-ball update) over M workers. Matrix products run at
``highest`` precision; ``dtype=bfloat16`` gives the lower-precision
control. Gradients are taken over blocks of rows so that a step fits
beside nothing else on one chip.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# rows per block of the reference's gradient: one row of 1,024 tokens at a
# time keeps its activations beside nothing else on one chip
ROWS = 1


# ------------------------------------------------------------- weights
def init(cfg: dict, seed: int, dtype=jnp.float32) -> dict:
    """Seeded weights: N(0, 1) scaled by fan-in^-1/2, norms at one.

    Keys: one split of the seed into layers + 3 (embedding, head, unused,
    then one per layer); a layer's key splits into attention and MLP keys,
    which split into one key per matrix.
    """
    if cfg["tie_embeddings"] or cfg["qk_norm"] or cfg["activation"] != \
            "swiglu" or set(cfg["layer_pattern"]) != {"A"}:
        raise NotImplementedError("the reference covers the lm124m block")
    d, h, kh, hd, f, v, n = (cfg["d_model"], cfg["num_heads"],
                             cfg["num_kv_heads"], cfg["head_dim"],
                             cfg["d_ff"], cfg["vocab_size"],
                             cfg["num_layers"])
    keys = jax.random.split(jax.random.PRNGKey(seed), n + 3)
    nrm = jax.random.normal

    def layer(k):
        (k,) = jax.random.split(k, 1)
        k_attn, k_mlp = jax.random.split(k)
        ka = jax.random.split(k_attn, 4)
        km = jax.random.split(k_mlp, 3)
        ones = jnp.ones((d,), jnp.float32)
        return {"norm1": {"scale": ones}, "norm2": {"scale": ones},
                "mixer": {"wq": nrm(ka[0], (d, h * hd)) * d ** -0.5,
                          "wk": nrm(ka[1], (d, kh * hd)) * d ** -0.5,
                          "wv": nrm(ka[2], (d, kh * hd)) * d ** -0.5,
                          "wo": nrm(ka[3], (h * hd, d)) * (h * hd) ** -0.5},
                "ffn": {"wi": nrm(km[0], (d, f)) * d ** -0.5,
                        "wg": nrm(km[1], (d, f)) * d ** -0.5,
                        "wo": nrm(km[2], (f, d)) * f ** -0.5}}

    layers = [layer(keys[3 + i]) for i in range(n)]
    params = {"embed": nrm(keys[0], (v, d)) * d ** -0.5,
              "lm_head": nrm(keys[1], (d, v)) * d ** -0.5,
              "final_norm": {"scale": jnp.ones((d,), jnp.float32)},
              "blocks": {"l0": jax.tree_util.tree_map(
                  lambda *xs: jnp.stack(xs), *layers)}}
    return jax.tree_util.tree_map(lambda x: x.astype(dtype), params)


# ---------------------------------------------------------------- tokens
def batches(cfg: dict, seed: int, global_batch: int, seq_len: int,
            workers: int, steps: int, branch: int = 16) -> list:
    """``steps`` batches of (tokens, labels), each ``(workers, rows, T)``:
    a first-order Markov chain whose every state has ``branch`` successors
    drawn from ``default_rng(seed)``; walks start and step with
    ``default_rng(seed + 1)``."""
    v = cfg["vocab_size"]
    succ = np.random.default_rng(seed).integers(0, v, size=(v, branch),
                                                dtype=np.int32)
    rng = np.random.default_rng(seed + 1)
    out = []
    for _ in range(steps):
        toks = np.empty((global_batch, seq_len + 1), np.int32)
        toks[:, 0] = rng.integers(0, v, size=global_batch)
        pick = rng.integers(0, branch, size=(global_batch, seq_len))
        for t in range(seq_len):
            toks[:, t + 1] = succ[toks[:, t], pick[:, t]]
        shape = (workers, global_batch // workers, seq_len)
        out.append((toks[:, :-1].reshape(shape), toks[:, 1:].reshape(shape)))
    return out


# ---------------------------------------------------------------- model
def _rmsnorm(x, scale, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta):
    t, half = x.shape[1], x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(
        jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def loss(params, cfg: dict, tokens, labels):
    """Mean next-token cross-entropy over every position of ``tokens``."""
    b, t = tokens.shape
    h, kh, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    eps = cfg["rmsnorm_eps"]
    causal = jnp.tril(jnp.ones((t, t), bool))

    def block(x, p):
        a = _rmsnorm(x, p["norm1"]["scale"], eps)
        q = _rope((a @ p["mixer"]["wq"]).reshape(b, t, h, hd),
                  cfg["rope_theta"])
        k = _rope((a @ p["mixer"]["wk"]).reshape(b, t, kh, hd),
                  cfg["rope_theta"])
        v = (a @ p["mixer"]["wv"]).reshape(b, t, kh, hd)
        k = jnp.repeat(k, h // kh, axis=2)
        v = jnp.repeat(v, h // kh, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) \
            * hd ** -0.5
        s = jnp.where(causal, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        o = jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(b, t, h * hd)
        x = x + o @ p["mixer"]["wo"]
        c = _rmsnorm(x, p["norm2"]["scale"], eps)
        f = jax.nn.silu(c @ p["ffn"]["wg"]) * (c @ p["ffn"]["wi"])
        return x + f @ p["ffn"]["wo"], None

    x = params["embed"][tokens]
    x, _ = jax.lax.scan(block, x, params["blocks"]["l0"])
    x = _rmsnorm(x, params["final_norm"]["scale"], eps)
    logits = (x @ params["lm_head"]).astype(jnp.float32)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


def _leaf_norms(tree) -> np.ndarray:
    return np.asarray([float(jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32))))) for x in jax.tree_util.tree_leaves(tree)])


# ------------------------------------------------------------------ CHB
def train(cfg: dict, tr: dict, seed: int, steps: int = 3,
          dtype=jnp.float32, half: bool = False) -> dict:
    """``steps`` CHB steps from the seeded weights.

    ``half`` plants a fault for the limits' readings: each worker's loss
    and gradient over the first half of its rows only.

    Returns the mean worker loss of each step, each worker's bank leaf
    norms after the last step (``bank``, (M, leaves)), the leaf norms of
    the change theta^steps - theta^0 (``change``), each worker's first
    gradient leaf norms (``grad0``) and uplink counts (``uplinks``).
    """
    m = tr["num_workers"]
    alpha, beta = tr["alpha"], tr["beta"]
    eps1 = tr["eps1_scale"] / (alpha ** 2 * m ** 2)
    data = batches(cfg, seed, tr["global_batch"], tr["seq_len"], m, steps)

    @jax.jit
    def grad(params, tokens, labels):
        with jax.default_matmul_precision("highest"):
            nb = tokens.shape[0] // ROWS
            def one(carry, xs):
                lsum, gsum = carry
                l, g = jax.value_and_grad(loss)(params, cfg, *xs)
                return (lsum + l, jax.tree_util.tree_map(jnp.add, gsum, g)), None
            zero = jax.tree_util.tree_map(jnp.zeros_like, params)
            (lsum, gsum), _ = jax.lax.scan(one, (jnp.zeros((), jnp.float32),
                                                 zero),
                (tokens.reshape(nb, ROWS, -1), labels.reshape(nb, ROWS, -1)))
            return lsum / nb, jax.tree_util.tree_map(lambda g: g / nb, gsum)

    @jax.jit
    def update(theta, prev, agg):
        with jax.default_matmul_precision("highest"):
            return jax.tree_util.tree_map(
                lambda t, a, p: t - jnp.asarray(alpha, t.dtype) * a
                + jnp.asarray(beta, t.dtype) * (t - p), theta, agg, prev)

    sq = jax.jit(lambda t: sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                               for x in jax.tree_util.tree_leaves(t)))
    theta = init(cfg, seed, dtype)
    prev = theta
    bank = [jax.tree_util.tree_map(jnp.zeros_like, theta) for _ in range(m)]
    uplinks = np.zeros(m, np.int64)
    losses, grad0 = [], []
    for k in range(steps):
        ssq = float(sq(jax.tree_util.tree_map(jnp.subtract, theta, prev)))
        lsum = 0.0
        for w in range(m):
            tokens, labels = data[k]
            keep = tokens.shape[1] // 2 if half else tokens.shape[1]
            lw, g = grad(theta, jnp.asarray(tokens[w, :keep]),
                         jnp.asarray(labels[w, :keep]))
            lsum += float(lw)
            if k == 0:
                grad0.append(_leaf_norms(g))
            delta = jax.tree_util.tree_map(jnp.subtract, g, bank[w])
            if float(sq(delta)) > eps1 * ssq:
                bank[w] = g
                uplinks[w] += 1
            del g, delta
        losses.append(lsum / m)
        agg = bank[0]
        for w in range(1, m):
            agg = jax.tree_util.tree_map(jnp.add, agg, bank[w])
        theta, prev = update(theta, prev, agg), theta
        del agg
    theta0 = init(cfg, seed, dtype)
    change = _leaf_norms(jax.tree_util.tree_map(jnp.subtract, theta, theta0))
    return {"losses": np.asarray(losses),
            "bank": np.stack([_leaf_norms(b) for b in bank]),
            "change": change, "grad0": np.stack(grad0), "uplinks": uplinks}
