"""Ahead-of-time compiles of the CHB kernels for a TPU v5e.

The rest of the suite runs the kernels in the Pallas interpreter, which
accepts block shapes and SMEM loads that Mosaic (the TPU kernel compiler)
refuses. These tests lower every kernel through Mosaic for a described
``v5e:2x2`` topology, with no chip attached, at the widths the main path
hands them:

  * ``lm``: M=4 workers over the 32768 x 768 embedding leaf of
    ``chb-paper-lm-124m`` (the trainer / ``ComposedOptimizer.step`` width);
  * ``fleet``: one ``fed.run_mesh`` shard of M=10^5 clients x d=16
    (``ComposedOptimizer.shard_step``'s staged ``grid=(M, rows)`` kernels).

One ``fed.run_mesh`` shard round also compiles whole, at the shapes of
the benchmark's ``emnist62-mlr`` population (M=3400 writers, W 784 x 62,
b 62), to read from its HLO that the client bank stays in the kernels'
tiles between rounds.

The fused megakernels hold the whole worker axis in one VMEM block, so
they compile at the ``lm`` width only: ``shard_step`` never calls them.
The server half of a ``fed.run_mesh`` round compiles over all four chips
of the topology, where a Mosaic kernel must not be left to the compiler's
automatic partitioning.

A new kernel joins this file. The topology is described inside a fixture,
never at import: only one process may load the TPU compiler library, and
the suite runs under several xdist workers.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro import opt
from repro.core.simulator import FedTask
from repro.fed.mesh import (MeshScenario, cohort_capacity, make_server_round,
                            make_shard_round)
from repro.kernels import (censor, common, fused_step, hb_update, lowrank_ef,
                           quantize_ef, topk_pack)

WIDTHS = {"lm": (4, (32768, 768)), "fleet": (100_000, (16,))}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs go to /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # the chip path runs with x64 off: Mosaic refuses the 64-bit block
    # indices x64 gives, and an earlier test in this process may have
    # turned it on
    x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    yield desc
    jax.config.update("jax_enable_x64", x64)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return compiled


# (name, fn(*args), arity of (M, leaf) operands, number of (M,) scalars)
_BATCHED = {
    "censor_delta_sqnorm_batched": (
        lambda g, h: censor.censor_delta_sqnorm_batched(g, h, interpret=False),
        2, 0),
    "sqnorm_batched": (
        lambda x: censor.sqnorm_batched(x, interpret=False), 1, 0),
    "censor_bank_advance": (
        lambda g, h, mk: censor.censor_bank_advance(g, h, mk,
                                                    interpret=False), 2, 1),
    "bank_advance": (
        lambda h, q, mk: censor.bank_advance(h, q, mk, interpret=False),
        2, 1),
    "absmax_batched": (
        lambda x: quantize_ef.absmax_batched(x, interpret=False), 1, 0),
    "quantize_ef_batched": (
        lambda p, e, mk, sc: quantize_ef.quantize_ef_batched(
            p, e, mk, sc, interpret=False), 2, 2),
    "int8_stats_batched": (
        lambda g, h, e: fused_step.int8_stats_batched(g, h, e,
                                                      interpret=False), 3, 0),
    "select_pack_ef_batched": (
        lambda p, e, k, mk: topk_pack.select_pack_ef_batched(
            p, e, k, mk, interpret=False), 3, 1),
    "residual_ef_batched": (
        lambda p, q, e, mk: lowrank_ef.residual_ef_batched(
            p, q, e, mk, interpret=False), 3, 1),
}


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("kernel", sorted(_BATCHED))
def test_batched_kernel_compiles(one_chip, kernel, width):
    fn, n_leaves, n_scalars = _BATCHED[kernel]
    m, leaf = WIDTHS[width]
    args = [_shape(one_chip, (m,) + leaf)] * n_leaves \
        + [_shape(one_chip, (m,))] * n_scalars
    _compile(fn, *args)


def test_fused_dense_step_compiles(one_chip):
    m, leaf = WIDTHS["lm"]
    bank, theta, mask = (_shape(one_chip, (m,) + leaf),
                         _shape(one_chip, leaf), _shape(one_chip, (m,)))
    _compile(lambda g, h, t, p, mk, a, b: fused_step.fused_dense_step(
        g, h, t, p, mk, a, b, interpret=False),
        bank, bank, theta, theta, mask, _shape(one_chip, ()),
        _shape(one_chip, ()))


def test_fused_int8_step_compiles(one_chip):
    m, leaf = WIDTHS["lm"]
    bank, theta, mask = (_shape(one_chip, (m,) + leaf),
                         _shape(one_chip, leaf), _shape(one_chip, (m,)))
    _compile(lambda g, h, e, t, p, mk, sc, a, b: fused_step.fused_int8_step(
        g, h, e, t, p, mk, sc, a, b, interpret=False),
        bank, bank, bank, theta, theta, mask, mask, _shape(one_chip, ()),
        _shape(one_chip, ()))


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_row_kernels_compile(one_chip, width):
    _, leaf = WIDTHS[width]
    x = _shape(one_chip, leaf)
    scalar = _shape(one_chip, ())
    _compile(lambda g, h: censor.censor_delta_sqnorm(g, h, interpret=False),
             x, x)
    _compile(lambda g, h, t: censor.censor_select(g, h, t, interpret=False),
             x, x, _shape(one_chip, (), jnp.int32))
    _compile(lambda t, n, p, a, b: hb_update.hb_update(t, n, p, a, b,
                                                       interpret=False),
             x, x, x, scalar, scalar)


@pytest.mark.parametrize("quantize", [None, "int8"])
def test_composed_pallas_step_compiles(one_chip, monkeypatch, quantize):
    # the step resolves interpret=None through interpret_default(), which
    # sees this process's CPU backend; steer it to Mosaic for the compile
    monkeypatch.setattr(common, "interpret_default", lambda: False)
    m = 4
    leaves = {"w": (768, 3072), "b": (768,)}
    params = {k: _shape(one_chip, v) for k, v in leaves.items()}
    grads = {k: _shape(one_chip, (m,) + v) for k, v in leaves.items()}
    o = opt.make("chb", 0.03, m, eps1_scale=4.0, quantize=quantize,
                 backend="pallas")
    state = jax.tree_util.tree_map(
        lambda x: _shape(one_chip, x.shape, x.dtype),
        jax.eval_shape(o.init, params))
    _compile(o.step, state, params, grads)


def test_fed_mesh_server_round_compiles_on_four_chips(topo, monkeypatch):
    monkeypatch.setattr(common, "interpret_default", lambda: False)
    k, m, d = 4, 100_000, 16
    mesh = Mesh(np.array(topo.devices), ("clients",),
                axis_types=(AxisType.Auto,))
    rows = NamedSharding(mesh, PartitionSpec("clients"))
    rep = NamedSharding(mesh, PartitionSpec())
    f32 = jnp.float32
    stacked = tuple(jax.ShapeDtypeStruct(shape, dt, sharding=rows) for
                    shape, dt in (((k, d), f32), ((k,), f32),
                                  ((k,), jnp.int32), ((k,), jnp.int32),
                                  ((k,), jnp.int32), ((k,), f32)))
    theta = jax.ShapeDtypeStruct((d,), f32, sharding=rep)
    o = opt.make("chb", 0.5 / m, m, eps1=4.0, backend="pallas")
    compiled = jax.jit(make_server_round(o, mesh, 0.5),
                       out_shardings=rep).lower(stacked, theta,
                                                theta).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text


def test_fed_mesh_cohort_round_steps_only_its_rows(one_chip, monkeypatch):
    """The one-chip shard round of the benchmark's cohort traffic (about
    100 of 3400 writers drawn a round) at emnist62-mlr's shapes, donated:
    the gradient and both bank kernels run on the gathered cohort's C
    rows, and neither gathering the rows nor putting them back moves a
    bank- or image-sized array."""
    monkeypatch.setattr(common, "interpret_default", lambda: False)
    m, pixels, classes, samples = 3400, 784, 62, 512
    scenario = MeshScenario(participation=1 / 34, loss_prob=0.1,
                            quorum=0.8, seed=2**31 + 1)
    o = opt.make("chb", 0.05, m, beta=0.4, eps1_scale=0.5, backend="pallas")
    c = cohort_capacity(m, scenario, o.censor)
    assert c == 160
    params = {"W": _shape(one_chip, (pixels, classes)),
              "b": _shape(one_chip, (classes,))}
    state = jax.tree_util.tree_map(
        lambda x: _shape(one_chip, x.shape, x.dtype),
        jax.eval_shape(o.shard_init, params))
    data = {"x": _shape(one_chip, (m, samples, pixels)),
            "y": _shape(one_chip, (m, samples), jnp.int32),
            "n": _shape(one_chip, (m,), jnp.int32)}
    fn = make_shard_round(o, _mlr_task(params, 671_585), scenario)
    text = jax.jit(fn, donate_argnums=(0,)).lower(
        state, params, data, _shape(one_chip, (m,), jnp.uint32),
        _shape(one_chip, (m,)), _shape(one_chip, (m,)),
        _shape(one_chip, (), jnp.int32)).compile().as_text()

    kernels = [line for line in text.splitlines()
               if "tpu_custom_call" in line and " custom-call(" in line]
    names = {re.search(r"kernels/(\w+)/pallas_call", line).group(1)
             for line in kernels}
    assert names == {"tree_delta_sqnorms", "tree_censor_bank_advance"}
    for line in kernels:
        result = line.split(" custom-call(")[0]
        operands = re.search(r"operand_layout_constraints=\{(.*?)\}, ",
                             line).group(1)
        leading = {int(dims.split(",")[0])
                   for dims in _SHAPE.findall(result + operands)
                   if "," in dims}
        assert leading == {c}, line[:300]
        # tiles held in HBM, as at full size, so the kernels' own time
        # holds their reads (kernels.common.in_hbm)
        assert '"input_memory_space_colors"' in line, line[:300]
    # the client gradient xᵀ·r of every writer is gone; only the loss
    # that makes the objective still reads every writer
    assert f"f32[{m},{pixels},{classes}]" not in text
    # nothing bank- or image-sized is re-laid out, cut into pieces or
    # rounded: the bank is put back in place, and the images are read
    # one row at a time
    moved = [op for op in _bank_sized(text, m, pixels * classes)
             if op in ("pad", "copy", "slice", "convert")]
    assert moved == [], moved


def _mlr_task(params, images: int):
    """emnist62-mlr's task: each writer's summed cross-entropy over its
    padded samples, scaled by the population's sample count."""
    scale = 1.0 / images

    def logits(p, d):
        return d["x"] @ p["W"] + p["b"]

    def valid(d):
        return (jnp.arange(d["y"].shape[0]) < d["n"]).astype(jnp.float32)

    def grad_fn(p, d):
        z = jax.nn.softmax(logits(p, d), axis=-1)
        onehot = jax.nn.one_hot(d["y"], z.shape[-1], dtype=z.dtype)
        r = (z - onehot) * (valid(d) * scale)[:, None]
        return {"W": d["x"].T @ r, "b": jnp.sum(r, axis=0)}

    def loss_fn(p, d):
        z = logits(p, d)
        onehot = jax.nn.one_hot(d["y"], z.shape[-1], dtype=z.dtype)
        per = jax.nn.logsumexp(z, axis=-1) - jnp.sum(z * onehot, axis=-1)
        return jnp.sum(per * valid(d)) * scale

    return FedTask(init_params=params, grad_fn=grad_fn, loss_fn=loss_fn,
                   worker_data=None)


_SHAPE = re.compile(r"\b[a-z]+[0-9]*\[([0-9,]*)\]")
_INSTR = re.compile(r"\s*(?:ROOT\s+)?%[\w.\-]+\s*=\s*.*?\s([a-z][\w\-]*)\(")


def _bank_sized(text: str, m: int, width: int) -> list:
    """Opcodes of the instructions, fused ones included, whose result or
    operand holds ``m`` rows of at least ``width`` elements."""
    ops = []
    for line in text.splitlines():
        body = line.split(", metadata=")[0]
        found = _INSTR.match(body)
        if found is None:
            continue
        for dims in _SHAPE.findall(body):
            d = [int(x) for x in dims.split(",") if x]
            if len(d) > 1 and d[0] == m and math.prod(d[1:]) >= width:
                ops.append(found.group(1))
                break
    return ops


def test_fed_mesh_shard_round_keeps_bank_in_tiles(one_chip, monkeypatch):
    """The one-chip shard round at emnist62-mlr's shapes, its bank held as
    tiles and donated as the benchmark runs it: the only Pallas calls are
    the round's kernels, the bank advances in its own buffer, and of the
    bank-sized relayouts only the fresh gradient's tiling is left."""
    monkeypatch.setattr(common, "interpret_default", lambda: False)
    m, pixels, classes, samples = 3400, 784, 62, 512
    params = {"W": _shape(one_chip, (pixels, classes)),
              "b": _shape(one_chip, (classes,))}
    o = opt.make("chb", 0.05, m, beta=0.4, eps1_scale=0.5, backend="pallas")
    assert o.bank_tiles
    state = jax.tree_util.tree_map(
        lambda x: _shape(one_chip, x.shape, x.dtype),
        jax.eval_shape(o.shard_init, params))
    assert state.ghat["W"].shape == (m, 512, 128)
    data = {"x": _shape(one_chip, (m, samples, pixels)),
            "y": _shape(one_chip, (m, samples), jnp.int32),
            "n": _shape(one_chip, (m,), jnp.int32)}
    fn = make_shard_round(o, _mlr_task(params, 671_585), MeshScenario())
    text = jax.jit(fn, donate_argnums=(0,)).lower(
        state, params, data, _shape(one_chip, (m,), jnp.uint32),
        _shape(one_chip, (m,)), _shape(one_chip, (m,)),
        _shape(one_chip, (), jnp.int32)).compile().as_text()

    kernels = [line for line in text.splitlines()
               if "tpu_custom_call" in line and " custom-call(" in line]
    scopes = [re.search(r"kernels/(\w+)/pallas_call", line)
              for line in kernels]
    assert kernels and all(scopes), kernels
    assert {s.group(1) for s in scopes} <= {
        "tree_delta_sqnorms", "tree_censor_bank_advance", "tree_hb_update"}
    advance = [line for line, s in zip(kernels, scopes)
               if s.group(1) == "tree_censor_bank_advance"]
    assert len(advance) == 2       # W and b
    assert all("output_to_operand_aliasing={{}: (2," in line
               for line in advance), advance
    relayouts = [op for op in _bank_sized(text, m, pixels * classes)
                 if op in ("pad", "copy")]
    assert relayouts.count("pad") <= 1 and relayouts.count("copy") <= 2, \
        relayouts
