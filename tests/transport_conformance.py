"""Transport conformance suite: every registered transport, one contract.

Each transport kind in ``opt.TRANSPORT_KINDS`` must pass every test here
— adding a new stage to the registry automatically enrolls it (the
parametrization reads the registry at collection time), so the
backend × surface × spec matrix can't silently grow an uncovered cell.

The contract, per transport:

  * reference ↔ pallas compute the same run (``exactness.assert_same_run``)
    at f32 and f64 on the pinned linreg task and on a pytree (NN) task
    with matrix leaves;
  * the row entry points (``prepare_row``/``encode_row``/
    ``feedback_row``, what ``repro.fed`` drives per client) agree with
    the matching worker slice of the batched step;
  * error-feedback residuals telescope: ``payload + new_err == pending``
    after a transmit — *bitwise* for ``exact_residual`` transports
    (dense/int8/top-k: each residual entry is an exact float subtraction
    by a Sterbenz-style argument, or exactly ``pending``/0), to
    tolerance for low-rank (its reconstruction is an arbitrary float);
  * ``payload_bytes`` is a static Python int and the split-int32
    ``CommStats`` counters accumulate it exactly past 2^24 bytes (where
    a single f32 cell would silently saturate);
  * specs round-trip through JSON with hyperparameters intact;
  * metrics collection is read-only (bit-identical trajectories on/off);
  * a quantize axis over the kind sweeps as ONE compiled program per
    static partition, and a task-scaled transport instance on the
    ``base_cfg`` survives the sweep (the engine must not clobber it with
    kind defaults).

Plus kernel-level pins (top-k select/pack + EF, low-rank EF residual):
pallas bit-identical to the ``ref.py`` oracle at f32/f64 — including
negative-zero handling — and the row entry draw-exact vs the M=1 batched
slice.

This module must NOT force ``jax_enable_x64``: CI runs it with
``JAX_ENABLE_X64`` 0 and 1, and the f64 tests skip at runtime when x64
is off. (Under the full tier-1 suite other modules enable x64 first.)
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import exactness
from repro import opt, sweep
from repro.core import simulator
from repro.core.accounting import CommStats
from repro.core.util import tree_worker_slice
from repro.data import paper_tasks
from repro.kernels import (censor, fused_step, hb_update, lowrank_ef,
                           quantize_ef, ref, topk_pack)

M = 5
ITERS = 40

# conformance-scale hyperparameters: small enough that compression is
# actually lossy on the d=20 golden task (k >= d would be a dense no-op)
CONFORMANCE_KW = {"topk": {"k": 8}, "lowrank": {"rank": 2}}
KINDS = sorted(opt.TRANSPORT_KINDS)


def make_transport(kind):
    return opt.make_transport(kind, **CONFORMANCE_KW.get(kind, {}))


def x64_enabled() -> bool:
    return bool(jax.config.read("jax_enable_x64"))


def require_x64():
    if not x64_enabled():
        pytest.skip("f64 leg needs JAX_ENABLE_X64=1")


@pytest.fixture(scope="module")
def linreg():
    return paper_tasks.make_linear_regression(m=M, n_per=30, d=20, seed=0)


def _cast_tree(tree, dtype):
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating)
        else x, tree)


def _as_f32(task):
    return task._replace(init_params=_cast_tree(task.init_params,
                                                jnp.float32),
                         worker_data=_cast_tree(task.worker_data,
                                                jnp.float32))


@pytest.fixture(scope="module")
def task32(linreg):
    return _as_f32(linreg.task)


def _chb(alpha, kind, backend="reference"):
    return opt.make("chb", alpha, M, transport=make_transport(kind),
                    backend=backend)


# ------------------------------------------------------------ registry
def test_registry_has_at_least_four_transports():
    assert len(opt.transport_names()) >= 4
    assert {"dense", "int8", "topk", "lowrank"} <= set(opt.transport_names())


def test_unknown_transport_kind_raises():
    with pytest.raises(ValueError, match="unknown quantize mode"):
        opt.make_transport("int4")
    with pytest.raises(ValueError, match="unknown quantize mode"):
        sweep.ConfigGrid(alpha=[0.1], quantize=["int4"])


# ------------------------------------------------------ backend parity
@pytest.mark.parametrize("kind", KINDS)
def test_backend_bitwise_f32(linreg, task32, kind):
    exactness.assert_same_run(
        simulator.run(_chb(linreg.alpha_paper, kind), task32, ITERS),
        simulator.run(_chb(linreg.alpha_paper, kind, "pallas"), task32,
                      ITERS))


@pytest.mark.parametrize("kind", KINDS)
def test_backend_bitwise_f64(linreg, kind):
    require_x64()
    exactness.assert_same_run(
        simulator.run(_chb(linreg.alpha_paper, kind), linreg.task, ITERS),
        simulator.run(_chb(linreg.alpha_paper, kind, "pallas"), linreg.task,
                      ITERS))


@pytest.mark.parametrize("kind", KINDS)
def test_pytree_task_bitwise(kind):
    """Matrix leaves (the NN task) exercise the low-rank factor path and
    per-leaf top-k selection; both backends must still compute one run."""
    bn = paper_tasks.make_neural_network(m=4, n_per=40, d=8, hidden=6)
    t32 = _as_f32(bn.task)
    t = make_transport(kind)
    o_ref = opt.make("chb", 0.02, 4, transport=t)
    o_pal = opt.make("chb", 0.02, 4, transport=t, backend="pallas")
    exactness.assert_same_run(simulator.run(o_ref, t32, 25),
                              simulator.run(o_pal, t32, 25))


# ------------------------------------------------------ row vs batched
def _rand_tree(key, m=None):
    """A two-leaf params pytree (matrix + vector); stacked when m given."""
    k1, k2 = jax.random.split(key)
    lead = () if m is None else (m,)
    return {"w": jax.random.normal(k1, lead + (6, 16), jnp.float32),
            "b": jax.random.normal(k2, lead + (16,), jnp.float32)}


@pytest.mark.parametrize("kind", KINDS)
def test_row_matches_batched_worker_slice(kind):
    """encode_row/feedback_row == the matching worker slice of the batched
    encode/feedback, for transmitted workers (the fed runtime only applies
    feedback on delivered uploads)."""
    t = make_transport(kind)
    params = _rand_tree(jax.random.PRNGKey(0))
    delta = _rand_tree(jax.random.PRNGKey(1), m=M)
    err = t.init(params, M)
    mask = jnp.asarray([1.0, 0.0, 1.0, 1.0, 0.0], jnp.float32)
    pending = t.prepare(delta, err)
    payload, aux = t.encode(pending, err)
    new_err = t.feedback(mask, pending, payload, aux, err)
    for i in range(M):
        err_row = tree_worker_slice(err, i) if t.stateful else ()
        d_row = tree_worker_slice(delta, i)
        p_row = t.prepare_row(d_row, err_row)
        for a, b in zip(jax.tree_util.tree_leaves(p_row),
                        jax.tree_util.tree_leaves(
                            tree_worker_slice(pending, i))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        q_row, aux_row = t.encode_row(p_row, err_row)
        for a, b in zip(jax.tree_util.tree_leaves(q_row),
                        jax.tree_util.tree_leaves(
                            tree_worker_slice(payload, i))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        if not t.stateful or not mask[i]:
            continue
        ne_row = t.feedback_row(p_row, q_row, aux_row, err_row)
        for a, b in zip(jax.tree_util.tree_leaves(ne_row),
                        jax.tree_util.tree_leaves(
                            tree_worker_slice(new_err, i))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------ EF residual telescoping
@pytest.mark.parametrize("kind", KINDS)
def test_ef_residual_telescopes(kind):
    """Chained steps: after every transmit, ``payload + new_err`` equals
    the pending delta — exactly for ``exact_residual`` transports, to
    tolerance for low-rank — and censored workers carry their residual
    forward unchanged. Nothing is ever lost, only deferred."""
    t = make_transport(kind)
    params = _rand_tree(jax.random.PRNGKey(2))
    delta = _rand_tree(jax.random.PRNGKey(3), m=M)
    err = t.init(params, M)
    masks = [jnp.asarray(v, jnp.float32) for v in
             ([1, 1, 1, 1, 1], [1, 0, 1, 0, 1], [0, 0, 0, 0, 0],
              [1, 1, 0, 1, 1])]
    for mask in masks:
        pending = t.prepare(delta, err)
        payload, aux = t.encode(pending, err)
        new_err = t.feedback(mask, pending, payload, aux, err)
        if t.stateful:
            bank_old = t.ef_bank(err)
            bank_new = t.ef_bank(new_err)
            mk = np.asarray(mask)
            for p, q, e0, e1 in zip(
                    jax.tree_util.tree_leaves(pending),
                    jax.tree_util.tree_leaves(payload),
                    jax.tree_util.tree_leaves(bank_old),
                    jax.tree_util.tree_leaves(bank_new)):
                p, q = np.asarray(p), np.asarray(q)
                e0, e1 = np.asarray(e0), np.asarray(e1)
                tx = mk != 0
                if t.exact_residual:
                    np.testing.assert_array_equal(q[tx] + e1[tx], p[tx])
                else:
                    exactness.assert_close(q[tx] + e1[tx], p[tx],
                                           "lossy_residual")
                np.testing.assert_array_equal(e1[~tx], e0[~tx])
        err = new_err


# --------------------------------------------------------- byte counters
# hyperparameters scaled so every transport ships a large payload (the
# counter contract is about magnitude, not compression)
BYTE_KW = {"topk": {"k": 1 << 21}, "lowrank": {"rank": 2}}


@pytest.mark.parametrize("kind", KINDS)
def test_byte_counter_exact_past_2_24(kind):
    """``payload_bytes`` is a static Python int and the split-int32
    counters stay exact beyond 2^24 bytes — where a single f32 counter
    cell loses integer precision and small increments stop registering."""
    t = opt.make_transport(kind, **BYTE_KW.get(kind, {}))
    params = {"w": jnp.zeros((1024, 1024), jnp.float32),
              "b": jnp.zeros((1 << 20,), jnp.float32)}
    pb = t.payload_bytes(params)
    assert isinstance(pb, int) and pb > 0
    cs = CommStats.init(M)
    mask = jnp.ones((M,), jnp.float32)
    steps = (1 << 24) // (pb * M) + 3
    for _ in range(steps):
        cs = cs.update(mask, pb)
    expected = steps * M * pb
    assert expected > 1 << 24
    assert cs.uplink_bytes_exact() == expected


# ------------------------------------------------------------ spec wire
@pytest.mark.parametrize("kind", KINDS)
def test_spec_roundtrip_json(kind):
    o = _chb(0.05, kind)
    spec = opt.to_spec(o)
    assert spec["transport"]["kind"] == kind
    for key, val in CONFORMANCE_KW.get(kind, {}).items():
        assert spec["transport"][key] == val
    assert opt.from_spec(spec) == o
    assert opt.from_spec(json.loads(json.dumps(spec))) == o


# --------------------------------------------------- metrics read-only
@pytest.mark.parametrize("kind", KINDS)
def test_metrics_read_only_bit_identity(linreg, task32, kind):
    o = _chb(linreg.alpha_paper, kind)
    h_off = simulator.run(o, task32, 25)
    h_on = simulator.run(o, task32, 25, collect_metrics=True)
    exactness.assert_bitwise(h_off, h_on)
    if make_transport(kind).stateful:
        key = f"transport/{kind}/ef_residual_sqnorm"
        assert key in h_on.metrics, sorted(h_on.metrics)


# ------------------------------------------------------------ sweep axis
@pytest.mark.parametrize("kind", KINDS)
def test_sweep_one_program_and_base_transport_survives(linreg, task32, kind):
    """A quantize axis over one kind compiles ONE program, and the
    base_cfg's task-scaled transport instance (k=8 / rank=2, not the kind
    defaults) is the one the sweep actually runs. Per-point trajectories
    match ``simulator.run`` bitwise at f64 (the PR-2 exactness contract);
    at f32 traced-vs-static hyperparameters agree only to the ulp, for
    every transport alike."""
    a = linreg.alpha_paper
    base = _chb(a, kind)
    grid = sweep.ConfigGrid(alpha=[a, 0.5 * a], beta=[0.4], eps1=[0.5],
                            quantize=[kind])
    task = linreg.task if x64_enabled() else task32
    res = sweep.run_sweep(grid, task, num_iters=25, base_cfg=base)
    assert res.num_programs == 1
    for i, pt in enumerate(res.points):
        assert res.specs[i]["transport"] == opt.to_spec(base)["transport"]
        o = base.with_hparams(alpha=pt.alpha, beta=pt.beta, eps1=pt.eps1)
        h = simulator.run(o, task, 25)
        if x64_enabled():
            exactness.assert_bitwise(h, res.history(i))
        else:
            exactness.assert_close_run(h, res.history(i), "traced_f32")


# ----------------------------------------------------- kernel-level pins
def _kernel_inputs(dtype, seed=0):
    key = jax.random.PRNGKey(seed)
    k1, k2 = jax.random.split(key)
    pending = jax.random.normal(k1, (4, 300), dtype)
    # salt in negative zeros: a multiply-based select (x * keep) would
    # flip their sign and break bit-parity with the reference
    pending = pending.at[:, 7].set(jnp.asarray(-0.0, dtype))
    err = jax.random.normal(k2, (4, 300), dtype) * 0.1
    mask = jnp.asarray([1.0, 0.0, 1.0, 1.0], jnp.float32)
    return pending, err, mask


@pytest.mark.parametrize("dtype", ["float32", "float64"])
def test_topk_kernel_matches_oracle(dtype):
    if dtype == "float64":
        require_x64()
    dt = jnp.dtype(dtype)
    pending, err, mask = _kernel_inputs(dt)
    from repro.opt.transport import tree_topk_keep
    keep = tree_topk_keep(pending, 32)
    got_q, got_e = topk_pack.select_pack_ef_batched(pending, err, keep,
                                                    mask)
    want_q, want_e = ref.select_pack_ef_batched(pending, err, keep, mask)
    for got, want in ((got_q, want_q), (got_e, want_e)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(np.signbit(np.asarray(got)),
                                      np.signbit(np.asarray(want)))
    # row entry: draw-exact vs the M=1 slice of the batched call
    row_q, row_e = topk_pack.select_pack_ef_row(pending[2], err[2], keep[2])
    full_q, full_e = topk_pack.select_pack_ef_batched(
        pending, err, keep, jnp.ones((4,), jnp.float32))
    np.testing.assert_array_equal(np.asarray(row_q), np.asarray(full_q[2]))
    np.testing.assert_array_equal(np.asarray(row_e), np.asarray(full_e[2]))


@pytest.mark.parametrize("dtype", ["float32", "float64"])
def test_lowrank_kernel_matches_oracle(dtype):
    if dtype == "float64":
        require_x64()
    dt = jnp.dtype(dtype)
    pending, err, mask = _kernel_inputs(dt, seed=1)
    payload = pending * 0.75     # stand-in reconstruction
    got = lowrank_ef.residual_ef_batched(pending, payload, err, mask)
    want = ref.residual_ef_batched(pending, payload, err, mask)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    row = lowrank_ef.residual_ef_row(pending[1], payload[1], err[1])
    full = lowrank_ef.residual_ef_batched(pending, payload, err,
                                          jnp.ones((4,), jnp.float32))
    np.testing.assert_array_equal(np.asarray(row), np.asarray(full[1]))


# ------------------------------------------------- fused-step conformance
# The one-sweep megakernel (kernels/fused_step.py) is the default pallas
# route for dense and int8+EF; topk/lowrank keep the staged chain. Every
# kind is enrolled here: the trajectory tests hold fused and
# force_staged() to one run (a no-op for the staged kinds, a real
# contract for the fused ones), and the kernel pins compare the
# megakernel against the staged kernel chain AND the ref.py oracle,
# element-for-element.
@pytest.mark.parametrize("kind", KINDS)
def test_fused_matches_staged_trajectory_f32(linreg, task32, kind):
    o = _chb(linreg.alpha_paper, kind, "pallas")
    h_fused = simulator.run(o, task32, ITERS)
    with fused_step.force_staged():
        h_staged = simulator.run(o, task32, ITERS)
    exactness.assert_same_run(h_fused, h_staged)


@pytest.mark.parametrize("kind", ["dense", "int8"])
def test_fused_matches_staged_trajectory_f64(linreg, kind):
    require_x64()
    o = _chb(linreg.alpha_paper, kind, "pallas")
    h_fused = simulator.run(o, linreg.task, ITERS)
    with fused_step.force_staged():
        h_staged = simulator.run(o, linreg.task, ITERS)
    exactness.assert_same_run(h_fused, h_staged)


@pytest.mark.parametrize("kind", ["dense", "int8"])
def test_fused_metrics_read_only(linreg, task32, kind):
    """Metrics collection must stay read-only on the fused route too."""
    o = _chb(linreg.alpha_paper, kind, "pallas")
    exactness.assert_bitwise(simulator.run(o, task32, 25),
                             simulator.run(o, task32, 25,
                                           collect_metrics=True))


def _fused_inputs(dtype, m=5, seed=4):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    g = jax.random.normal(keys[0], (m, 300), dtype)
    # salt negative zeros: the censored rows of the bank advance and the
    # quantizer's round-trip must preserve their sign bit
    g = g.at[:, 11].set(jnp.asarray(-0.0, dtype))
    ghat = jax.random.normal(keys[1], (m, 300), dtype) * 0.5
    err = jax.random.normal(keys[2], (m, 300), dtype) * 0.1
    theta = jax.random.normal(keys[3], (300,), dtype)
    prev = theta - jax.random.normal(keys[4], (300,), dtype) * 0.01
    mask = jnp.asarray([1.0, 0.0, 1.0, 1.0, 0.0][:m], jnp.float32)
    return g, ghat, err, theta, prev, mask


@pytest.mark.parametrize("dtype", ["float32", "float64"])
def test_fused_dense_kernel_matches_staged_and_oracle(dtype):
    if dtype == "float64":
        require_x64()
    g, ghat, _, theta, prev, mask = _fused_inputs(jnp.dtype(dtype))

    # ONE compiled program computes all three routes — how they coexist
    # in real use (the whole step is inside one scan jit), and the only
    # granularity at which XLA's FMA-contraction choices are pinned: a
    # separately-jitted epilogue may contract ``t - alpha*agg``
    # differently from the same expression inlined next to the staged
    # kernels (the trajectory tests cover the cross-program contract)
    @jax.jit
    def all_routes(g, ghat, theta, prev, mask):
        alpha, beta = 0.05, 0.4
        fused = fused_step.fused_dense_step(g, ghat, theta, prev, mask,
                                            alpha, beta)
        # staged kernel chain: bank advance -> eq.(5) sum -> eq.(4) kernel
        ng = censor.censor_bank_advance(g, ghat, mask)
        agg = jnp.sum(ng, axis=0)
        staged = (ng, agg,
                  hb_update.hb_update(theta, agg, prev, alpha, beta))
        oracle = ref.fused_dense_step(g, ghat, theta, prev, mask,
                                      alpha, beta)
        return fused, staged, oracle

    got, staged, want = all_routes(g, ghat, theta, prev, mask)
    for got_x, staged_x, want_x in zip(got, staged, want):
        np.testing.assert_array_equal(np.asarray(got_x),
                                      np.asarray(staged_x))
        np.testing.assert_array_equal(np.asarray(got_x), np.asarray(want_x))
        np.testing.assert_array_equal(np.signbit(np.asarray(got_x)),
                                      np.signbit(np.asarray(want_x)))


@pytest.mark.parametrize("dtype", ["float32", "float64"])
def test_fused_int8_kernel_matches_staged_and_oracle(dtype):
    if dtype == "float64":
        require_x64()
    g, ghat, err, theta, prev, mask = _fused_inputs(jnp.dtype(dtype),
                                                    seed=5)

    # one compiled program for all routes (see the dense test above)
    @jax.jit
    def all_routes(g, ghat, err, theta, prev, mask):
        alpha, beta = 0.05, 0.4
        # sweep 1: the stats kernel vs the staged pending materialization
        sq, am = fused_step.int8_stats_batched(g, ghat, err)
        pending = (g.astype(ghat.dtype) - ghat) + err.astype(ghat.dtype)
        staged_stats = (censor.sqnorm_batched(pending),
                        quantize_ef.absmax_batched(pending))
        scale = jnp.where(am > 0, am / 127.0, 1.0).astype(jnp.float32)
        # sweep 2: the megakernel vs the staged chain and the oracle
        fused = fused_step.fused_int8_step(g, ghat, err, theta, prev,
                                           mask, scale, alpha, beta)
        payload, ne = quantize_ef.quantize_ef_batched(pending, err, mask,
                                                      scale)
        ng = censor.bank_advance(ghat, payload, mask)
        agg = jnp.sum(ng, axis=0)
        staged = (ng, ne, agg,
                  hb_update.hb_update(theta, agg, prev, alpha, beta))
        oracle = ref.fused_int8_step(g, ghat, err, theta, prev, mask,
                                     scale, alpha, beta)
        return (sq, am), staged_stats, fused, staged, oracle, payload, \
            pending

    ((got_sq, got_am), staged_stats, got, staged, want, payload,
     pending) = all_routes(g, ghat, err, theta, prev, mask)
    np.testing.assert_array_equal(np.asarray(got_sq),
                                  np.asarray(staged_stats[0]))
    np.testing.assert_array_equal(np.asarray(got_am),
                                  np.asarray(staged_stats[1]))
    for got_x, staged_x, want_x in zip(got, staged, want):
        np.testing.assert_array_equal(np.asarray(got_x),
                                      np.asarray(staged_x))
        np.testing.assert_array_equal(np.asarray(got_x), np.asarray(want_x))
    # EF telescoping survives fusion: the staged payload (bitwise what
    # the megakernel applies in-register) plus the fused residual
    # reconstructs pending on transmitted workers — exactly at f64; at
    # f32 the final ``payload + err`` re-rounding can cost an ulp on
    # arbitrary (pending, err) data, so only closeness is asserted here
    # (test_ef_residual_telescopes pins the exact f32 contract on the
    # transport's own chained construction, which the fused route
    # reproduces bitwise via the staged-equality asserts above)
    tx = np.asarray(mask) != 0
    recon = np.asarray(payload)[tx] + np.asarray(got[1])[tx]
    if dtype == "float64":
        np.testing.assert_array_equal(recon, np.asarray(pending)[tx])
    else:
        exactness.assert_close(recon, np.asarray(pending)[tx],
                               "fused_residual_f32")


@pytest.mark.parametrize("dtype", ["float32", "float64"])
def test_fused_kernels_row_slice_draw_exact(dtype):
    """M=1 single-worker runs of the megakernels reproduce the matching
    worker slice of the M=5 batched call bit-for-bit (the property the
    ``repro.fed`` event runtime's per-client sends rely on)."""
    if dtype == "float64":
        require_x64()
    g, ghat, err, theta, prev, _ = _fused_inputs(jnp.dtype(dtype), seed=6)
    ones = jnp.ones((g.shape[0],), jnp.float32)
    one = jnp.ones((1,), jnp.float32)
    full = fused_step.fused_dense_step(g, ghat, theta, prev, ones,
                                       0.05, 0.4)
    sq_f, am_f = fused_step.int8_stats_batched(g, ghat, err)
    scale = jnp.where(am_f > 0, am_f / 127.0, 1.0).astype(jnp.float32)
    full8 = fused_step.fused_int8_step(g, ghat, err, theta, prev, ones,
                                       scale, 0.05, 0.4)
    for i in range(g.shape[0]):
        row = fused_step.fused_dense_step(
            g[i:i + 1], ghat[i:i + 1], theta, prev, one, 0.05, 0.4)
        np.testing.assert_array_equal(np.asarray(row[0][0]),
                                      np.asarray(full[0][i]))
        sq_r, am_r = fused_step.int8_stats_batched(
            g[i:i + 1], ghat[i:i + 1], err[i:i + 1])
        np.testing.assert_array_equal(np.asarray(sq_r[0]),
                                      np.asarray(sq_f[i]))
        np.testing.assert_array_equal(np.asarray(am_r[0]),
                                      np.asarray(am_f[i]))
        row8 = fused_step.fused_int8_step(
            g[i:i + 1], ghat[i:i + 1], err[i:i + 1], theta, prev, one,
            scale[i:i + 1], 0.05, 0.4)
        np.testing.assert_array_equal(np.asarray(row8[0][0]),
                                      np.asarray(full8[0][i]))
        np.testing.assert_array_equal(np.asarray(row8[1][0]),
                                      np.asarray(full8[1][i]))


# ------------------------------------- int8+EF property tests (hypothesis)
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:              # pragma: no cover - CI installs hypothesis
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:
    finite = st.floats(-1e6, 1e6, allow_nan=False, allow_infinity=False,
                       width=32)

    @settings(max_examples=50, deadline=None)
    @given(xs=st.lists(finite, min_size=1, max_size=64),
           seed=st.integers(0, 100))
    def test_property_int8_roundtrip_reconstructs_f64(xs, seed):
        """f64 quantize→dequantize + residual reconstructs the input
        EXACTLY: payload + new_err == pending bitwise (the residual
        subtraction is exact — Sterbenz lemma territory — because payload
        and pending share an exponent window)."""
        require_x64()
        t = opt.make_transport("int8")
        pending = jnp.asarray(xs, jnp.float64)[None]
        err = jnp.zeros_like(pending)
        mask = jnp.ones((1,), jnp.float32)
        payload, aux = t.encode(pending, err)
        new_err = t.feedback(mask, pending, payload, aux, err)
        np.testing.assert_array_equal(
            np.asarray(payload) + np.asarray(new_err), np.asarray(pending))

    @settings(max_examples=50, deadline=None)
    @given(xs=st.lists(finite, min_size=1, max_size=64),
           steps=st.integers(2, 8))
    def test_property_int8_ef_residual_bounded_constant_input(xs, steps):
        """Repeated application to a constant input: each round's residual
        is bounded elementwise by half the round's quantization step
        (scale/2, from round-to-nearest), so the EF bank never accumulates
        — and re-encoding the SAME pending is idempotent (the unchained
        residual sequence is trivially monotone)."""
        t = opt.make_transport("int8")
        delta = jnp.asarray(xs, jnp.float32)[None]
        err = jnp.zeros_like(delta)
        mask = jnp.ones((1,), jnp.float32)
        for _ in range(steps):
            pending = t.prepare(delta, err)
            payload, aux = t.encode(pending, err)
            err = t.feedback(mask, pending, payload, aux, err)
            amax = float(jnp.max(jnp.abs(pending)))
            scale = amax / 127.0 if amax > 0 else 1.0
            bound = 0.5 * scale * (1 + 1e-6) + 1e-30
            assert float(jnp.max(jnp.abs(err))) <= bound
        # idempotence: encoding the same pending twice gives one residual
        p1, _ = t.encode(pending, err)
        p2, _ = t.encode(pending, err)
        np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))
else:   # pragma: no cover - dev-deps-only skip marker
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_property_int8_ef():
        pass


# ------------------------------------------------- benchmark curve smoke
def test_benchmark_transport_curves_and_spec_roundtrip():
    """``compare_algorithms`` grows one ``chb_<kind>`` curve per non-dense
    registry transport, each carrying a ``from_spec``-able registry spec
    whose task-scaled transport hyperparameters survived the sweep."""
    from benchmarks import common as bcommon

    bundle = paper_tasks.make_linear_regression(m=4, n_per=20, d=10, seed=0)
    res = bcommon.compare_algorithms(
        bundle, num_iters=200, tol=1e-3, fstar_iters=2000,
        transports=("int8", "topk", "lowrank"))
    curves = [a for a in bcommon.CURVES if a in res]
    assert curves == bcommon.CURVES
    for name in curves:
        spec = res[name]["spec"]
        rebuilt = opt.from_spec(spec)
        assert opt.to_spec(rebuilt) == spec
        assert isinstance(res[name]["uplink_bytes"], int)
    # the task-scaled instances (not the registry defaults) are what ran
    n = bcommon.task_params_count(bundle.task)
    assert res["chb_topk"]["spec"]["transport"]["k"] == max(1, 2 * n // 5)
    assert res["chb_lowrank"]["spec"]["transport"]["rank"] == 2
