"""Substrate coverage: checkpointing, data pipeline, sharding rules,
HLO analyzer, accounting."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import checkpoint as ckpt
from repro.core.accounting import CommStats
from repro.data import lm_data
from repro.launch import hlo_analysis as ha
from repro.launch import sharding as shr


# ------------------------------------------------------------- checkpoint
def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(12.0).reshape(3, 4),
            "b": {"c": jnp.ones((5,), jnp.int32),
                  "d": jnp.asarray(2.5, jnp.bfloat16)}}
    path = os.path.join(tmp_path, "ck")
    ckpt.save(path, tree, metadata={"step": 7})
    like = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    out = ckpt.restore(path, like)
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(out)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    assert ckpt.load_metadata(path)["step"] == 7


def test_checkpoint_shape_mismatch_rejected(tmp_path):
    path = os.path.join(tmp_path, "ck2")
    ckpt.save(path, {"a": jnp.ones((3,))})
    with pytest.raises(AssertionError):
        ckpt.restore(path, {"a": jax.ShapeDtypeStruct((4,), jnp.float32)})


# ------------------------------------------------------------------- data
def test_markov_lm_is_learnable_and_deterministic():
    lm1 = lm_data.MarkovLM(vocab_size=64, branch=4, seed=3)
    lm2 = lm_data.MarkovLM(vocab_size=64, branch=4, seed=3)
    np.testing.assert_array_equal(lm1.next_tokens, lm2.next_tokens)
    rng = np.random.default_rng(0)
    toks = lm1.sample(rng, 8, 100)
    # every transition must be one of the 4 successors of the previous state
    for b in range(8):
        for t in range(100):
            assert toks[b, t + 1] in lm1.next_tokens[toks[b, t]]
    assert lm1.entropy_floor() == pytest.approx(np.log(4))


def test_batch_iterator_worker_chunking():
    from repro.configs import get
    cfg = get("chb-paper-lm-124m").reduced()
    it = lm_data.batch_iterator(cfg, global_batch=8, seq_len=16,
                                num_workers=4)
    b = next(it)
    assert b["tokens"].shape == (4, 2, 16)
    assert b["labels"].shape == (4, 2, 16)
    # labels are next-token shifted
    flat_t = np.asarray(b["tokens"]).reshape(8, 16)
    flat_l = np.asarray(b["labels"]).reshape(8, 16)
    np.testing.assert_array_equal(flat_t[:, 1:], flat_l[:, :-1])


# --------------------------------------------------------- sharding rules
class _FakeMesh:
    axis_names = ("data", "model")
    shape = {"data": 16, "model": 16}


def test_param_spec_rules():
    m = _FakeMesh()
    # 2D weight: fsdp x tp
    assert tuple(shr.param_spec("['blocks']['l0']['mixer']['wq']",
                                (1, 4096, 8192), m)) == \
        (None, "data", "model")
    # norm: replicated
    assert tuple(shr.param_spec("['blocks']['l0']['norm1']['scale']",
                                (1, 4096), m)) == (None, None)
    # non-divisible dims fall back to None
    spec = shr.param_spec("['embed']", (50280, 1536), m)
    assert tuple(spec) == (None, "model")
    # gather-safe embeddings: single-axis only
    spec = shr.param_spec("['embed']", (151936, 4096), m, gather_safe=True)
    assert tuple(spec) == (None, "model")
    spec = shr.param_spec("['embed']", (151936, 4096), m)
    assert tuple(spec) == ("data", "model")


# ------------------------------------------------------------ hlo analyzer
def test_hlo_analyzer_scan_trip_counts():
    W = jnp.ones((32, 32))
    x = jnp.ones((4, 32))

    def scanned(x, Ws):
        def body(h, w):
            return jnp.tanh(h @ w), None
        return jax.lax.scan(body, x, Ws)[0]

    Ws = jnp.stack([W] * 5)
    txt = jax.jit(scanned).lower(x, Ws).compile().as_text()
    r = ha.analyze(txt)
    assert r["flops"] == 5 * 2 * 4 * 32 * 32
    assert r["collective_bytes"] == 0


def test_hlo_analyzer_grad_through_scan():
    W = jnp.ones((16, 16))
    x = jnp.ones((2, 16))

    def loss(x, Ws):
        def body(h, w):
            return jnp.tanh(h @ w), None
        return jnp.sum(jax.lax.scan(body, x, Ws)[0] ** 2)

    Ws = jnp.stack([W] * 3)
    txt = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(x, Ws)\
        .compile().as_text()
    r = ha.analyze(txt)
    # fwd (3 dots) + bwd (2 dots per step: dh and dW)
    assert r["flops"] == 9 * 2 * 2 * 16 * 16


def test_shape_bytes_parse():
    assert ha.shape_bytes("bf16[8,128]{1,0}") == 8 * 128 * 2
    assert ha.shape_bytes("(f32[4]{0}, s32[2,2]{1,0})") == 16 + 16
    assert ha.shape_bytes("pred[]") == 1


# ------------------------------------------------------------- accounting
def test_comm_stats_savings():
    s = CommStats.init(4)
    for _ in range(10):
        s = s.update(jnp.asarray([1.0, 0.0, 0.0, 0.0]), payload_bytes=100)
    assert int(s.total_uplinks) == 10
    assert float(s.savings_vs_dense()) == pytest.approx(0.75)
    assert float(s.uplink_bytes) == pytest.approx(1000.0)
    assert int(s.downlink_count) == 10


# ---------------------------------------------------------- compile cache
@pytest.mark.parametrize("placed", [True, False])
def test_compile_cache_placement(tmp_path, monkeypatch, placed):
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    if placed:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        where = compile_cache.enable_compile_cache()
        now = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    if placed:   # JAX reads the variable itself; nothing is set in code
        assert where == str(tmp_path) and now == before
    else:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert where == now == os.path.join(root, ".jax_cache")
