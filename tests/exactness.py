"""The exactness contract of the test suite, stated once.

Every test that compares a run with another run, or with numbers recorded
from an earlier run, goes through this module. It holds four kinds of
comparison.

``assert_same_run(h1, h2)`` — two programs that must compute the same run
(reference and pallas backends, fused and staged kernels, ``run_mesh`` and
``simulator.run``), in one process:

  * decisions are bitwise: censor masks, uplink counts, bytes,
    participation, deliveries and quorum;
  * the trajectory is bitwise: objective, final params and final bank;
  * diagnostics — ``agg_grad_sqnorm``, an f32 sum of squares that the step
    reports and never reads — agree within ``DIAGNOSTIC_MAX_ULP`` ulps.
    The two programs fuse that reduction into different neighbours, and
    XLA:CPU does not promise one grouping of a fused reduction from one
    program, or one machine, to the next. Measured on the paper tasks: 0
    ulps in most comparisons, at most 2 (the NN task).

``assert_bitwise(h1, h2)`` — runs that must be identical in every field,
diagnostics included: donation on and off, metrics on and off, a sweep's
rows against per-point runs of the same backend, the int8 sync anchor.

``assert_close_run(h1, h2, bound)`` — two computations of one run that
are allowed to round differently (another gradient batching, traced
hyperparameters, a batched cohort): decisions bitwise as above, the named
float fields within ``BOUNDS[bound]``. ``assert_close(a, b, bound)``
holds two arrays to the same table. Each entry of ``BOUNDS`` says why its
two sides may round apart.

``assert_matches_pin(history, pin)`` — a run against a ``Pin`` in ``PINS``,
numbers recorded from an earlier run, possibly on another machine. It
holds only what every machine computes the same:

  * the uplinks of every iteration before the run's numerical floor
    (``numerical_floor``) and the uplink bytes through it, bitwise. From
    the floor on, the censor compares quantities that are themselves
    rounding noise, and machines may decide differently there;
  * the final objective and the objective's sum over the run, within
    ``PIN_RTOL``. The final params are not pinned: after the floor a run
    whose slowest mode has not converged ends where its late decisions
    took it (the top-k run's params differ by 4.6e-4 between two machines
    whose objectives agree to an ulp).

A pinned run is run with ``collect_metrics=True``: its floor is read from
the ``step_sqnorm`` and ``comm/uplink_bytes`` series, which collection adds
without changing any other bit (``assert_bitwise`` holds metrics on and
off to that).
"""
from typing import NamedTuple

import jax
import numpy as np

#: ulps by which a diagnostic reduction may differ between two programs.
DIAGNOSTIC_MAX_ULP = 4

#: rtol of a pin's floats: 4 ulps of the run's objective dtype. The pins'
#: final objectives and objective sums differ by at most 1.5 ulps between
#: machines and code generations (measured: the recordings against this
#: code's runs; XLA:CPU at opt level 0 and at SSE4.2 against the default).
#: The nearest wrong pin is 17 ulps off (the f32 dense and int8 runs'
#: objective sums); ``uplink_bytes`` tells those two apart exactly anyway.
PIN_RTOL = {np.dtype(dt): 4 * np.finfo(dt).eps
            for dt in (np.float32, np.float64)}

#: ulps of ``||theta_K||`` below which a step counts as rounding noise
#: (see ``numerical_floor``).
STEP_ULPS = 1000

# Decision fields, compared bitwise wherever both histories' types define
# them; every history type defines the first three.
_REQUIRED = ("mask", "comm_cum", "objective")
_DECISIONS = ("mask", "comm_cum", "participated", "attempted", "delivered",
              "delivered_cum", "quorum_met", "bytes_cum")


class Bound(NamedTuple):
    rtol: float
    atol: float


#: Float bounds of two computations of one run that may round apart; the
#: decisions are still bitwise. Each says what differs between the sides.
BOUNDS = {
    # fed.run_edge's sync anchor evaluates each client's gradient on its
    # own row, the simulator vmaps them over the workers
    "event_runtime": Bound(1e-9, 0.0),
    # the same on the NN task, whose two sigmoid layers each round per
    # row on that side
    "event_runtime_pytree": Bound(1e-8, 0.0),
    # run_mesh(bake_data=False) passes the task data as an argument, so
    # XLA may group the loss and gradient reductions differently from the
    # baked program
    "argument_data": Bound(1e-12, 0.0),
    # run_mesh's per-round MetricBag, merged over its shards, against the
    # simulator's bag: each is computed inside another program
    "mesh_metrics": Bound(1e-12, 0.0),
    # leaves of more than 256*128 elements per worker: the kernels sum
    # per-tile partials and XLA contracts the reference's elementwise
    # chains in its own way, and those ulps compound over 25 steps
    # (docs/kernels.md, "The numerics contract, precisely")
    "multitile": Bound(1e-3, 1e-9),
    # an f32 sweep row, whose hyperparameters are traced operands,
    # against the same point run with them as constants: the two
    # programs fuse the f32 arithmetic differently
    "traced_f32": Bound(1e-5, 0.0),
    # sweep(vectorize=True) batches the points' matmuls into one
    "vectorized": Bound(1e-6, 1e-8),
    # a gathered cohort round computes the same rows' gradients in a
    # batch of C rows instead of all M
    "cohort_gather": Bound(1e-5, 1e-7),
    # the profiler on and off: host spans around the same program
    "profiler": Bound(1e-6, 0.0),
    # the sharded trainer's losses against its unsharded reference: the
    # data and model axes split every reduction
    "sharded_trainer": Bound(2e-4, 2e-4),
    # the simulator against the literal numpy transcription of
    # Algorithm 1
    "literal_algorithm1": Bound(1e-8, 1e-8),
    # per-tensor against global censoring on a one-leaf task: the same
    # decisions, the eq.-(8) sums taken per leaf
    "per_tensor_one_leaf": Bound(1e-10, 0.0),
    # payload + residual against the pending gradient, for a transport
    # whose residual is not exact (low-rank's P @ Q^T)
    "lossy_residual": Bound(1e-5, 1e-6),
    # the fused route's f32 payload + residual, which rounds once more
    # than the pending gradient it reconstructs
    "fused_residual_f32": Bound(1e-6, 1e-7),
}


def leaves_equal(a, b):
    """Two pytrees with the same structure, leaf for leaf bitwise."""
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _defined(h, name):
    return name in type(h)._fields


def _bank(h):
    if _defined(h, "final_state"):
        return h.final_state.ghat
    if _defined(h, "final_bank"):
        return h.final_bank
    return None


def _compare_decisions(h1, h2):
    for name in _REQUIRED:
        for h in (h1, h2):
            assert getattr(h, name, None) is not None, (
                f"{type(h).__name__} carries no {name}")
    for name in _DECISIONS:
        if _defined(h1, name) and _defined(h2, name):
            np.testing.assert_array_equal(np.asarray(getattr(h1, name)),
                                          np.asarray(getattr(h2, name)),
                                          err_msg=name)


def _compare(h1, h2, diagnostic_max_ulp):
    _compare_decisions(h1, h2)
    np.testing.assert_array_equal(np.asarray(h1.objective),
                                  np.asarray(h2.objective),
                                  err_msg="objective")
    leaves_equal(h1.final_params, h2.final_params)
    bank1, bank2 = _bank(h1), _bank(h2)
    if bank1 is not None and bank2 is not None:
        leaves_equal(bank1, bank2)
    a = np.asarray(h1.agg_grad_sqnorm)
    b = np.asarray(h2.agg_grad_sqnorm)
    if diagnostic_max_ulp == 0:
        np.testing.assert_array_equal(a, b, err_msg="agg_grad_sqnorm")
    else:
        # a host-side history may hold the f32 stat widened to f64
        dt = min(a.dtype, b.dtype, key=lambda d: d.itemsize)
        np.testing.assert_array_max_ulp(a.astype(dt), b.astype(dt),
                                        maxulp=diagnostic_max_ulp)


def assert_same_run(h1, h2):
    """Two programs, one run: decisions and trajectory bitwise,
    diagnostics within ``DIAGNOSTIC_MAX_ULP``."""
    _compare(h1, h2, DIAGNOSTIC_MAX_ULP)


def assert_bitwise(h1, h2):
    """Every field bitwise, diagnostics included."""
    _compare(h1, h2, 0)


def assert_close(actual, desired, bound: str, err_msg=""):
    """Two arrays within ``BOUNDS[bound]``."""
    rtol, atol = BOUNDS[bound]
    np.testing.assert_allclose(np.asarray(actual), np.asarray(desired),
                               rtol=rtol, atol=atol, err_msg=err_msg)


def assert_close_run(h1, h2, bound: str, floats=("objective",)):
    """Two computations of one run: decisions bitwise, ``floats`` within
    ``BOUNDS[bound]``."""
    _compare_decisions(h1, h2)
    for name in floats:
        assert_close(getattr(h1, name), getattr(h2, name), bound,
                     err_msg=name)


# ------------------------------------------------------------------- pins
class Pin(NamedTuple):
    """What a pinned run reproduces on every machine."""
    uplinks: str          # uplinks of each iteration before the floor
    uplink_bytes: int     # uplink bytes through the last of them
    objective: float      # f(theta) at the last iteration
    objective_sum: float  # f(theta) summed over the run
    step_ulps: float = STEP_ULPS  # the floor's bound (``numerical_floor``)


def _metric(history, name):
    assert history.metrics, "run a pinned run with collect_metrics=True"
    return np.asarray(history.metrics[name])


def numerical_floor(history, step_ulps: float = STEP_ULPS) -> int:
    """The iteration at which a run reaches its numerical floor.

    That is the first iteration k >= 1 whose step ``||theta^k -
    theta^{k-1}||`` — the root of the ``ssq`` that the eq.-(8) censor
    scales by eps1 and compares with each worker's ``dsq`` — is within
    ``step_ulps`` ulps of ``||theta_K||`` (ulps of the objective's dtype).
    The step is a difference of two rounded params, so from there on
    ``ssq`` carries a relative rounding error of about ``1/step_ulps``,
    and so do the ``dsq`` of the gradients the steps produce. A decision
    whose margin is below that may go either way on another machine.
    With ``STEP_ULPS`` = 1000 the floor comes ten or more iterations
    before the first decision that another machine took differently, in
    every pinned run (measured against 22 copies of each chb run with one
    label moved an ulp, and against XLA:CPU at opt level 0 and at
    SSE4.2):

      ======================  =====  ==============================
      run                     floor  decisions first part at
      ======================  =====  ==============================
      chb, f32, 60 iters        21   31-41 (copies), 32 (SSE4.2),
                                     37 (opt level 0)
      chb int8, f32             21   32-35, 32, 33
      chb, f64, 80 iters        69   79 (4 of 22 copies; both
                                     code generations)
      chb per-tensor, f64       71   never
      hb, f64                   64   never (code generations)
      gd, lag, adaptive, NN     none never (code generations)
      ======================  =====  ==============================

    A pin may state a larger ``step_ulps`` for a run that amplifies
    rounding. Top-k does: which coordinates ship is a decision too, its
    params on two code generations drift apart by about 1.2 times an
    iteration (10^4 ulps by iteration 49), and its decisions part at
    53-55 (copies) and 59 (opt level 0) while its step is still 10^4
    ulps; its pin holds it to 10^5 ulps, floor 41.
    """
    obj = np.asarray(history.objective)
    eps = np.finfo(obj.dtype).eps
    ssq = _metric(history, "step_sqnorm").astype(np.float64)
    theta_sq = sum(float(np.sum(np.square(np.asarray(x, np.float64))))
                   for x in jax.tree_util.tree_leaves(history.final_params))
    below = np.flatnonzero(ssq[1:] < (step_ulps * eps) ** 2 * theta_sq)
    return int(below[0]) + 1 if below.size else len(obj)


def fingerprint(history, step_ulps: float = STEP_ULPS) -> Pin:
    """The ``Pin`` of a run (uplinks counted from its masks, <= 9 workers)."""
    obj = np.asarray(history.objective)
    per_iter = np.asarray(history.mask).sum(axis=1).astype(int)
    assert per_iter.max(initial=0) <= 9, "one digit per iteration"
    floor = numerical_floor(history, step_ulps)
    nbytes = _metric(history, "comm/uplink_bytes")[floor - 1]
    return Pin(uplinks="".join(map(str, per_iter[:floor])),
               uplink_bytes=int(nbytes), objective=float(obj[-1]),
               objective_sum=float(obj.sum()), step_ulps=step_ulps)


def assert_matches_pin(history, pin: Pin):
    got = fingerprint(history, pin.step_ulps)
    assert (got.uplinks, got.uplink_bytes) == (pin.uplinks,
                                               pin.uplink_bytes), got
    rtol = PIN_RTOL[np.asarray(history.objective).dtype]
    np.testing.assert_allclose(
        [got.objective, got.objective_sum],
        [pin.objective, pin.objective_sum], rtol=rtol, atol=0,
        err_msg=str(got))


#: The task every linreg pin assumes: ``make_linear_regression(m=5,
#: n_per=30, d=20, seed=0).alpha_paper``; if it moves, the pins mean nothing.
LINREG_ALPHA_PAPER = 0.07822618699787157

# One table of pins. The floats are the recorded runs' own; the uplink
# strings and bytes were counted from the same runs on the current code.
# The f64 entries come from the pre-redesign monolithic ``chb.step`` (80
# iterations on the linreg task above at alpha_paper; ``nn_chb``: 25
# iterations of chb at alpha 0.02 on ``make_neural_network(m=4,
# n_per=40, d=8, hidden=6)``, whose init was drawn with jax's threefry
# in its non-partitionable mode, see ``paper_tasks.make_neural_network``),
# the ``f32/<transport>`` entries from the reference backend (60
# iterations of chb on the same task cast to f32; top-k at k=8, low-rank
# at rank 2, whose run ships vector leaves dense and so equals the dense
# one).
PINS = {
    "gd": Pin("5" * 80, 64000, 68.11928630009376, 5462.366757667825),
    "hb": Pin("5" * 64, 51200, 68.11928630009376, 5460.652987176616),
    "lag": Pin("5353534443534443534" + "4" * 61, 50880,
               68.11928630009378, 5462.142966267983),
    "chb": Pin("53444444444435353534443535353535435345444444444444444444"
               "5453535353535", 44320,
               68.11928630009378, 5460.696746983873),
    "chb_int8": Pin("534444444444353535344435353535354353454444444444444444"
                    "445453535353535", 6648,
                    68.11928630009378, 5460.705122071892),
    "chb_per_tensor": Pin("5344444444443535353444353535353543534544442512452"
                          "2554555555555555555555", 47040,
                          68.11928630009378, 5460.696746983873),
    "adaptive": Pin("50311100210201010021201012201300201030210301210130210"
                    "111020010212020210200303003", 13280,
                    68.12210672429018, 5476.6490451879345),
    "nn_chb": Pin("4443402201121022012010040", 19520,
                  5.003449354576052, 404.70609390186326),
    "f32/dense": Pin("534444444444353535344", 6640,
                     68.1192855834961, 4098.3115234375),
    "f32/int8": Pin("534444444444353535344", 1992,
                    68.1192855834961, 4098.31982421875),
    "f32/topk": Pin("55555555555555555555554555555554554545455", 12800,
                    68.11929321289062, 4103.3701171875, step_ulps=1e5),
    "f32/lowrank": Pin("534444444444353535344", 6640,
                       68.1192855834961, 4098.3115234375),
}
