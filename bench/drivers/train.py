"""Drive ``repro.train.trainer.train`` on a configuration from the registry.

The configuration file names a ``repro.configs`` architecture (``arch``)
and holds every field of its ``ModelConfig`` as it is run; the traffic file
holds the job (workers, batch, sequence length, CHB hyperparameters).

``train()`` builds its weights, data and compiled step from the seed on
every call and exposes no per-step hook, so set-up makes two calls: three
logged steps (which compile on a cold cache, and whose losses and state
the reference checks), then ``log_every + 1`` logged steps that time a warm
step. The window is one call of N steps, logged every ``log_every``, N
sized from those to last about ``--seconds``.

All three calls run one program from one seed, so they agree bit for bit
where they overlap: the timing call's first steps replay the checked
call's, and the window's losses and uplink counts at steps 0 and
``log_every`` replay the timing call's. The check requires that
(``window_drift``), which ties the window's own updates to the steps the
reference follows.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from bench import harness

CHECK_STEPS = 3


def model_config(cfg: dict):
    """The registry's ``ModelConfig`` with every field the file gives."""
    from repro.configs import get
    from repro.configs.base import ModelConfig

    fields = {f.name for f in dataclasses.fields(ModelConfig)} - {"name"}
    base = get(cfg["arch"])
    return dataclasses.replace(base, **{k: v for k, v in cfg.items()
                                        if k in fields}).validate()


def train_config(tr: dict, seed: int, steps: int, log_every: int):
    from repro.train.trainer import TrainConfig

    return TrainConfig(algorithm=tr["algorithm"], strategy="scan",
                       num_workers=tr["num_workers"], alpha=tr["alpha"],
                       beta=tr["beta"], eps1_scale=tr["eps1_scale"],
                       global_batch=tr["global_batch"], seq_len=tr["seq_len"],
                       steps=steps, log_every=log_every, seed=seed,
                       remat=tr["remat"])


def _leaf_norms(tree) -> np.ndarray:
    import jax
    import jax.numpy as jnp
    norm = jax.jit(lambda x: jnp.sqrt(jnp.sum(jnp.square(x))))
    return np.asarray([float(norm(x)) for x in jax.tree_util.tree_leaves(tree)])


def checked_steps(ctx, mcfg, out) -> dict:
    """The checked call's result, reduced to the numbers the reference is
    compared on; nothing of the program stays on the chip."""
    import jax

    from repro.models import model

    params, state, hist = out
    theta0 = jax.jit(model.init_params, static_argnums=1)(
        jax.random.PRNGKey(ctx.seed), mcfg)
    change = _leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a - b, params, theta0))
    del params, theta0
    m = ctx.cell.traffic["num_workers"]
    bank = np.stack([_leaf_norms(jax.tree_util.tree_map(lambda x: x[w],
                                                        state.ghat))
                     for w in range(m)])
    got = {"losses": np.asarray([h["loss"] for h in hist]),
           "bank": bank, "change": change,
           "uplinks": np.asarray(state.comm.uplink_count, np.int64)}
    del state
    return got


def drift(got: dict, timing: list, window: list) -> float:
    """How far the calls part where they ran the same steps: the timing
    call's first losses against the checked call's, and the window's logged
    losses and uplink totals against the timing call's at the same steps."""
    losses = np.asarray([h["loss"] for h in timing])
    n = len(got["losses"])
    out = float(np.max(np.abs(losses[:n] - got["losses"])))
    by_step = {h["step"]: h for h in timing}
    for h in window:
        if h["step"] in by_step:
            t = by_step[h["step"]]
            out = max(out, abs(h["loss"] - t["loss"]),
                      abs(h["comms"] - t["comms"]))
    return out


def compare(got: dict, ref: dict, window_drift: float, limits: dict) -> list:
    loss_gap = float(np.max(np.abs(got["losses"] - ref["losses"])
                            / np.abs(ref["losses"])))
    # a leaf's gap against the larger of its own norm and the median leaf's
    floor_b = np.median(ref["bank"], axis=1, keepdims=True)
    bank_gap = float(np.max(np.abs(got["bank"] - ref["bank"])
                            / np.maximum(ref["bank"], floor_b)))
    # leaves whose reference gradient is nought to rounding move by
    # round-off alone: left out of the change by rule, not by name
    g0 = ref["grad0"].mean(axis=0)
    moved = g0 >= 1e-3 * np.median(g0)
    floor_c = np.median(ref["change"][moved])
    change_gap = float(np.max(np.abs(got["change"] - ref["change"])[moved]
                              / np.maximum(ref["change"][moved], floor_c)))
    return [
        harness.Check("loss_gap", loss_gap, limits["loss_gap"]),
        harness.Check("bank_gap", bank_gap, limits["bank_gap"]),
        harness.Check("change_gap", change_gap, limits["change_gap"]),
        harness.Check("uplink_mismatch", float(np.sum(np.abs(
            got["uplinks"] - ref["uplinks"]))), 0.0),
        harness.Check("window_drift", window_drift, 0.0)]


def run(ctx: harness.Context) -> harness.Outcome:
    from repro.train.trainer import train

    cfg, tr = ctx.cell.config, ctx.cell.traffic
    mcfg = model_config(cfg)
    n = tr["log_every"] + 1
    # (steps, log_every): the checked call, the timing call, the window
    plan = [(CHECK_STEPS, 1), (n, 1), None]
    for i in range(len(plan)):
        window = plan[i] is None
        if window:
            # the history's wall clock is rounded to 0.1 s; a call too
            # short to show it is timed whole
            walls = [h["wall_s"] for h in timing]
            step_s = (walls[-1] - walls[1]) / (n - 2) or call_s / n
            fixed_s = call_s - n * step_s
            steps = max(n, int(round((ctx.seconds - max(fixed_s, 0.0))
                                     / step_s)))
            plan[i] = (steps, tr["log_every"])
            gc.collect()
            setup_s = time.perf_counter() - ctx.t_start
            ctx.log("warm", step_s=step_s, call_fixed_s=fixed_s, steps=steps,
                    setup_s=setup_s)
        # every call from this one line: see harness.entry_call
        out, call_s = harness.entry_call(
            ctx, train, mcfg, train_config(tr, ctx.seed, *plan[i]),
            verbose=False, window=window)
        if i == 0:
            got = checked_steps(ctx, mcfg, out)
            del out
            gc.collect()
            ctx.log("checked_call", seconds=call_s,
                    losses=got["losses"].tolist())
        elif i == 1:
            timing = out[2]
            del out
    params, state, hist = out
    window_s = call_s
    peak = harness.memory_peak(ctx.devices)
    ctx.log("window", seconds=window_s, steps=steps,
            call_fixed_s=window_s - steps * step_s)
    failed = int(np.sum(~np.isfinite([h["loss"] for h in hist])))
    del params, state
    gc.collect()

    t = time.perf_counter()
    ref = harness.config_part(cfg["name"], "ref").train(
        cfg, tr, ctx.seed, steps=CHECK_STEPS)
    ctx.log("reference", seconds=time.perf_counter() - t,
            losses=ref["losses"].tolist())
    checks = compare(got, ref, drift(got, timing, hist), cfg["limits"])

    tokens = steps * tr["global_batch"] * tr["seq_len"]
    return harness.Outcome(
        setup_s=setup_s, window_s=window_s, attempted=steps, failed=failed,
        end_to_end={"train_tokens_per_s": tokens / window_s}, checks=checks,
        work={"tokens": tokens, "flops_per_token": harness.config_part(
            cfg["name"], "work").flops_per_token(cfg, tr["seq_len"])},
        memory_peak_bytes=peak)
