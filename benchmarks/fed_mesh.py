"""Fed-mesh scaling: 10^5-client frontier + clients-vs-wall-clock ladder.

The mesh-sharded federated runtime (``repro.fed.mesh``, guide:
docs/fed_scaling.md) runs a federated sweep with >= 10^5 clients over the
devices of one host and reports

  * a **scenario frontier** — bytes / energy / wall-clock / accuracy for
    a small grid of deployment scenarios (participation, uplink loss,
    quorum) at 10^5 clients, with the accuracy target honest because the
    O(M*d) ``edge_quadratics`` task has a closed-form optimum; and
  * a **scaling ladder** — host wall-clock per synchronous round as the
    client count climbs to 10^6, the "does the client axis actually
    scale" story (``collect_mask=False``, ``bake_data=False`` — the
    documented 10^6-client knobs; masks and counts are unchanged).

The body runs in the calling process over every device JAX finds, so on
a TPU host it holds the chips itself. A CPU rehearsal gets its 8 host
devices from the command line:
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

Numbers land in ``BENCH_fed_mesh.json``; CI runs the fast shapes and
gates against the committed ``BENCH_fed_mesh_smoke.json`` baseline via
``tools/bench_diff.py``. In-benchmark assertions are the functional
gate: the ideal scenario must converge to f*, censoring must save bytes
versus transmit-everything, and every ladder rung must complete.
"""
import os
import time

import jax
import numpy as np

from repro import fed, opt
from repro.data import edge_tasks
from repro.launch import mesh as mk

# REPRO_BENCH_FAST=1: CI-smoke shapes — same code paths, tiny population
FAST = os.environ.get("REPRO_BENCH_FAST", "") not in ("", "0")

FRONTIER_M = 800 if FAST else 100_000
FRONTIER_ROUNDS = 60 if FAST else 80
LADDER_M = (400, 800, 1600) if FAST else (100_000, 250_000, 500_000,
                                          1_000_000)
LADDER_ROUNDS = 3 if FAST else 5

SCENARIOS = (("ideal", 1.0, 0.0, 1.0),
             ("lossy", 1.0, 0.2, 0.7),
             ("partial", 0.5, 0.0, 0.5),
             ("harsh", 0.5, 0.3, 0.5))


def _measure(mesh) -> dict:
    # ---- scenario frontier at FRONTIER_M clients ----------------------
    M, R = FRONTIER_M, FRONTIER_ROUNDS
    task = edge_tasks.make_edge_quadratics(M, d=16, seed=0)
    fstar = edge_tasks.edge_quadratics_fstar(task)
    # 0.5/M keeps alpha * L ~ mean(a)/2 < 1 at any M (curvatures are
    # log-uniform over [1, 3]). For the eq.-(8) censor, delta_sq tracks
    # a_m^2 * step_sq on a quadratic, so eps1=4 censors the flat half of
    # the curvature spread until their deltas accumulate — the frontier's
    # byte axis actually moves
    o = opt.make("chb", 0.5 / M, M, eps1=4.0)
    pop = fed.uniform_vector_population(M, compute_mean_s=0.05,
                                       straggler_frac=0.1, seed=1)
    chan = fed.ChannelConfig()
    en = fed.EnergyModel()
    payload = o.transport.payload_bytes(task.init_params)

    frontier = []
    for name, part, loss, quo in SCENARIOS:
        sc = fed.MeshScenario(participation=part, loss_prob=loss,
                              quorum=quo, seed=3)
        t0 = time.perf_counter()
        mh = fed.run_mesh(o, task, R, mesh=mesh, scenario=sc,
                          population=pop, channel=chan, energy=en,
                          collect_mask=False, bake_data=False)
        host_s = time.perf_counter() - t0
        frontier.append(dict(
            scenario=name, participation=part, loss_prob=loss,
            quorum=quo, rounds=R,
            uplink_bytes=int(mh.bytes_cum[-1]),
            attempted=int(mh.attempted.sum()),
            joules=float(mh.energy_cum[-1]),
            sim_wall_s=float(mh.wall_clock[-1]),
            host_s=round(host_s, 2),
            quorum_met_frac=float(mh.quorum_met.mean()),
            gap0=float(mh.objective[0] - fstar),
            gap=float(mh.objective[-1] - fstar)))

    # ---- clients-vs-wall-clock ladder ---------------------------------
    ladder = []
    for m in LADDER_M:
        t = edge_tasks.make_edge_quadratics(m, d=16, seed=0)
        ol = opt.make("chb", 0.5 / m, m, eps1=4.0)
        t0 = time.perf_counter()
        mh = fed.run_mesh(ol, t, LADDER_ROUNDS, mesh=mesh,
                          scenario=fed.MeshScenario(seed=0),
                          collect_mask=False, bake_data=False)
        total = time.perf_counter() - t0
        assert np.isfinite(mh.objective).all()
        ladder.append(dict(clients=m, rounds=LADDER_ROUNDS,
                           total_s=round(total, 2),
                           s_per_round=round(total / LADDER_ROUNDS, 3),
                           client_rounds_per_s=round(
                               m * LADDER_ROUNDS / total)))
    return dict(frontier=frontier, ladder=ladder, payload_bytes=payload,
                fstar=fstar)


def main() -> tuple[str, dict]:
    jax.config.update("jax_enable_x64", True)
    devices = jax.device_count()
    out = _measure(mk.make_client_mesh(devices))
    frontier, ladder = out["frontier"], out["ladder"]

    print(f"fed_mesh: {devices} {jax.default_backend()} devices, frontier at "
          f"{FRONTIER_M:,} clients, ladder to {LADDER_M[-1]:,}")
    print(f"{'scenario':>9} {'part':>5} {'loss':>5} {'quo':>4} "
          f"{'MBytes':>9} {'kJ':>8} {'sim_s':>8} {'gap/gap0':>9}")
    for row in frontier:
        rel = row["gap"] / row["gap0"]
        print(f"{row['scenario']:>9} {row['participation']:>5.2f} "
              f"{row['loss_prob']:>5.2f} {row['quorum']:>4.2f} "
              f"{row['uplink_bytes'] / 1e6:>9.2f} "
              f"{row['joules'] / 1e3:>8.2f} {row['sim_wall_s']:>8.1f} "
              f"{rel:>9.2e}")
    print(f"{'clients':>10} {'rounds':>6} {'s/round':>8} "
          f"{'client-rounds/s':>16}")
    for row in ladder:
        print(f"{row['clients']:>10,} {row['rounds']:>6} "
              f"{row['s_per_round']:>8.3f} "
              f"{row['client_rounds_per_s']:>16,}")

    # functional gates: the ideal scenario converges to the closed-form
    # optimum; censoring beats transmit-everything on bytes; every rung
    # of the ladder completed with finite objectives (asserted in-sub)
    ideal = frontier[0]
    assert ideal["scenario"] == "ideal"
    assert ideal["gap"] < 1e-3 * ideal["gap0"], \
        f"ideal scenario did not converge: {ideal}"
    naive = FRONTIER_M * FRONTIER_ROUNDS * out["payload_bytes"]
    assert ideal["uplink_bytes"] < naive, "censoring saved no bytes"
    assert [row["clients"] for row in ladder] == list(LADDER_M)
    # accuracy under deployment stress stays bounded: every scenario
    # improved on its starting gap
    assert all(row["gap"] < row["gap0"] for row in frontier)

    us = ladder[-1]["s_per_round"] * 1e6
    row = (f"fed_mesh,{us:.1f},"
           f"clients_max={LADDER_M[-1]};devices={devices};"
           f"ideal_relgap={ideal['gap'] / ideal['gap0']:.2e}")
    payload = dict(row=row, backend=jax.default_backend(), fast=FAST,
                   devices=devices, payload_bytes=out["payload_bytes"],
                   fstar=out["fstar"], frontier=frontier, ladder=ladder,
                   spec=None)
    return row, payload


if __name__ == "__main__":
    print(main()[0])
