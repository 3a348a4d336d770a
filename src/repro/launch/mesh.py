"""Production meshes. Functions, not module constants — importing this module
never touches jax device state (dryrun.py must set XLA_FLAGS first).

Every mesh here has Auto axes: the compiler propagates shardings, as the
trainer strategies and the client-sharded federated runtime assume.
``jax.make_mesh`` defaults to Explicit axes, under which an unannotated
op on a sharded operand (a Pallas call, an embedding gather) raises
``ShardingTypeError``, so the axis types are passed explicitly. Every
constructor checks the requested shape against the real device count and
raises with the fix spelled out — a mesh request that cannot be satisfied
must never silently degrade to fewer devices.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def _require_devices(needed: int, what: str) -> None:
    """Loud failure when a mesh wants more devices than the process has.

    ``jax.make_mesh`` also errors, but with a generic message; this one
    names the XLA_FLAGS escape hatch used by every multi-device test/bench
    in this repo (they run in subprocesses — see tests/test_distributed.py).
    """
    have = jax.device_count()
    if needed > have:
        raise ValueError(
            f"{what} needs {needed} devices but only {have} are visible; "
            "set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{needed} (in a fresh process, before jax initializes) or "
            "request a smaller mesh")


def make_auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes and a loud device-count check."""
    _require_devices(math.prod(shape), f"mesh {tuple(shape)}x{tuple(axes)}")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_client_mesh(num_shards: int):
    """1-D ``("clients",)`` mesh for the sharded federated runtime.

    The client axis of every bank pytree (``launch/sharding.py``
    ``client_*`` helpers) and the ``repro.fed.mesh`` round programs shard
    over this mesh. ``num_shards`` must not exceed the visible device
    count — requesting more errors loudly instead of degrading.
    """
    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    return make_auto_mesh((num_shards,), ("clients",))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; multi_pod stacks 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_local_mesh(model_parallel: int = 1, *, pods: int = 1):
    """Mesh over whatever devices exist (CPU tests / small runs)."""
    n = jax.device_count()
    if n % (model_parallel * pods) != 0:
        raise ValueError(
            f"device count {n} is not divisible by model_parallel="
            f"{model_parallel} * pods={pods}; adjust the factors or the "
            "forced host device count")
    if pods > 1:
        shape = (pods, n // (model_parallel * pods), model_parallel)
        axes = ("pod", "data", "model")
    else:
        shape = (n // model_parallel, model_parallel)
        axes = ("data", "model")
    return make_auto_mesh(shape, axes)


def dp_axes(mesh) -> tuple:
    """Axes used for batch/FSDP sharding (pod+data when present)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)
