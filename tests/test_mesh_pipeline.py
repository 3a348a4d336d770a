"""``run_mesh`` reads each round back one round late, in one batched fetch.

The host dispatches round k before it fetches round k-1's values, and
drains the last round before returning. These tests hold that schedule to
a plain serial loop written here from the same programs (dispatch a round,
read each of its values back, account for them, then the next round): the
two histories must be identical in every field, on the pallas dense tiled
route, the gathered cohort route and int8, with donated shard states, at
one and two shards.
"""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import exactness
from repro import opt
from repro.fed import ChannelConfig, EnergyModel
from repro.fed.clients import uniform_vector_population
from repro.fed.mesh import (MeshHistory, MeshScenario, cohort_capacity,
                            make_server_round, make_shard_round, run_mesh)
from repro.launch.mesh import make_client_mesh
from repro.launch.sharding import (client_shard_sizes, per_device_views,
                                   replicated_sharding, stack_shards)
from repro.obs.metrics import merge_shard_bags
from test_cohort_gather import M, SCENARIO, mlr_task

TESTS = os.path.dirname(os.path.abspath(__file__))
# drawn participation and loss with a quorum that fails some rounds; the
# cohort route's draws leave a cohort small enough to gather
DRAWN = dict(participation=0.7, loss_prob=0.3, quorum=0.6, seed=5)
ROUTES = {"dense-pallas": ({"backend": "pallas"}, DRAWN),
          "cohort": ({"backend": "pallas"}, SCENARIO),
          "int8": ({"backend": "pallas", "quantize": "int8"}, DRAWN)}
# (route, rounds, collect_mask, collect_metrics)
CASES = ([(r, n, True, False) for r in ROUTES for n in (1, 2, 5)]
         + [(r, 5, False, False) for r in ROUTES]
         + [(r, 2, True, True) for r in ROUTES])


def case_id(case) -> str:
    route, rounds, mask, metrics = case
    return f"{route}-R{rounds}" + ("" if mask else "-nomask") + \
        ("-metrics" if metrics else "")


def serial_run_mesh(o, task, rounds, *, mesh, scenario, population, channel,
                    collect_mask, collect_metrics):
    """``run_mesh(..., donate=True, bake_data=False)`` as a serial loop:
    each round is dispatched, then read back value by value and accounted
    for before the next round is dispatched."""
    devices = list(mesh.devices.flat)
    m_local = client_shard_sizes(o.num_workers, mesh)
    local = dataclasses.replace(o, num_workers=m_local)
    shard_prog = jax.jit(make_shard_round(local, task, scenario,
                                          collect_metrics=collect_metrics),
                         donate_argnums=(0,))
    rep = replicated_sharding(mesh)
    server_prog = jax.jit(make_server_round(o, mesh, scenario.quorum),
                          out_shardings=rep)
    copy_tree = jax.jit(lambda t: jax.tree_util.tree_map(jnp.copy, t))
    comp = np.asarray(population.compute_mean_s, np.float32)
    compw = np.asarray(population.compute_w, np.float32)
    blocks = []
    for i, dev in enumerate(devices):
        rows = slice(i * m_local, (i + 1) * m_local)
        blocks.append(jax.device_put((
            jax.tree_util.tree_map(lambda x: x[rows], task.worker_data),
            jnp.arange(rows.start, rows.stop, dtype=jnp.uint32),
            comp[rows], compw[rows]), dev))
    params = jax.device_put(task.init_params, rep)
    prev = jax.device_put(jax.tree_util.tree_map(jnp.copy, task.init_params),
                          rep)
    states = [jax.jit(local.shard_init)(jax.device_put(task.init_params, d))
              for d in devices]
    energy = EnergyModel()
    payload = o.transport.payload_bytes(task.init_params)
    uplink_air = channel.overhead_s + 8.0 * payload / channel.uplink_rate_bps
    downlink_air = channel.downlink_time(payload)

    h = {f: [] for f in ("objective", "gsq", "met", "part", "att", "del",
                         "wall", "energy", "mask", "bags")}
    t = joules = 0.0
    for k in range(rounds):
        views = per_device_views(params, mesh)
        outs = [shard_prog(states[i], views[i], *blocks[i], np.int32(k))
                for i in range(len(devices))]
        (params, prev, met, loss, gsq, n_part, n_att, n_del,
         comp_j) = server_prog(stack_shards([x[1] for x in outs], mesh),
                               params, prev)
        states = [x[0]._replace(prev_params=copy_tree(pv))
                  for x, pv in zip(outs, per_device_views(prev, mesh))]
        part, att = int(n_part), int(n_att)
        h["objective"].append(float(loss))
        h["gsq"].append(float(gsq))
        h["met"].append(bool(met))
        h["part"].append(part)
        h["att"].append(att)
        h["del"].append(int(n_del))
        t += (max(float(x[3]) for x in outs)
              + (uplink_air if att else 0.0) + downlink_air)
        h["wall"].append(t)
        joules += float(energy.round_energy(att, part, payload)) + \
            float(comp_j)
        h["energy"].append(joules)
        if collect_mask:
            h["mask"].append(np.concatenate(
                [np.asarray(x[2]) for x in outs]).astype(np.int8))
        if collect_metrics:
            merged = merge_shard_bags(
                [{key: np.asarray(v) for key, v in x[4].items()}
                 for x in outs], weights=[m_local] * len(devices))
            merged = {key: float(np.asarray(v)) for key, v in merged.items()}
            merged["agg_grad_sqnorm"] = h["gsq"][-1]
            h["bags"].append(merged)
    att = np.asarray(h["att"], np.int64)
    return MeshHistory(
        objective=np.asarray(h["objective"]),
        agg_grad_sqnorm=np.asarray(h["gsq"]),
        quorum_met=np.asarray(h["met"], bool),
        participated=np.asarray(h["part"], np.int64), attempted=att,
        delivered=np.asarray(h["del"], np.int64), comm_cum=np.cumsum(att),
        delivered_cum=np.cumsum(np.asarray(h["del"], np.int64)),
        bytes_cum=np.cumsum(att * payload),
        energy_cum=np.asarray(h["energy"]), wall_clock=np.asarray(h["wall"]),
        final_params=params,
        mask=np.stack(h["mask"]) if h["mask"] else None,
        metrics=tuple(h["bags"]))


def run_case(case, shards: int) -> dict:
    """Both loops on one case: the pipelined history and the serial one,
    and what the case exercises."""
    route, rounds, mask, metrics = case
    kw, draws = ROUTES[route]
    o = opt.make("chb", 0.5, M, beta=0.4, eps1_scale=0.5, **kw)
    task = mlr_task()
    common = dict(mesh=make_client_mesh(shards),
                  scenario=MeshScenario(**draws),
                  population=uniform_vector_population(
                      M, compute_mean_s=0.5, straggler_frac=0.2, seed=1),
                  channel=ChannelConfig(), collect_mask=mask,
                  collect_metrics=metrics)
    piped = run_mesh(o, task, rounds, donate=True, bake_data=False, **common)
    serial = serial_run_mesh(o, task, rounds, **common)
    return {"piped": piped, "serial": serial, "tiled": o.bank_tiles,
            "cohort_rows": cohort_capacity(
                M // shards, common["scenario"], o.censor,
                collect_metrics=metrics)}


def check_case(case, shards: int) -> str:
    """``"ok"`` where the pipelined history is the serial one's in every
    field and the case took the route it names, else what failed."""
    try:
        out = run_case(case, shards)
        exactness.assert_identical(out["piped"], out["serial"])
        route, rounds, mask, metrics = case
        assert len(out["piped"].objective) == rounds
        assert out["tiled"] == (route != "int8"), out["tiled"]
        # a metric bag reads every writer, so it turns the gather off
        assert (out["cohort_rows"] > 0) == (route == "cohort"
                                            and not metrics), out
    except AssertionError as e:
        return f"{type(e).__name__}: {e}"
    return "ok"


@pytest.mark.parametrize("case", CASES, ids=case_id)
def test_pipelined_history_is_the_serial_loops_one_shard(case):
    assert check_case(case, 1) == "ok"


@pytest.fixture(scope="module")
def two_shards():
    """Every case at K=2, in one child process that sees two CPU devices
    (this one keeps its single-device view)."""
    code = textwrap.dedent(f"""
        import json, sys
        sys.path.insert(0, {TESTS!r})
        from test_mesh_pipeline import CASES, case_id, check_case
        print(json.dumps({{case_id(c): check_case(c, 2) for c in CASES}}))
    """)
    env = {**os.environ, "PYTHONPATH": os.path.join(TESTS, "..", "src"),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=2"}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.splitlines()[-1])


@pytest.mark.parametrize("case", CASES, ids=case_id)
def test_pipelined_history_is_the_serial_loops_two_shards(two_shards, case):
    assert two_shards[case_id(case)] == "ok"


def test_zero_rounds_is_an_empty_history():
    o = opt.make("chb", 0.5, M, backend="pallas")
    h = run_mesh(o, mlr_task(), 0, scenario=MeshScenario(**DRAWN),
                 donate=True)
    for name in ("objective", "agg_grad_sqnorm", "quorum_met",
                 "participated", "attempted", "delivered", "comm_cum",
                 "delivered_cum", "bytes_cum", "energy_cum", "wall_clock"):
        assert getattr(h, name).shape == (0,), name
    assert h.mask is None and h.metrics == ()
    exactness.leaves_equal(h.final_params, mlr_task().init_params)
