"""Profiler spans: named host-side phases of the program's entry points.

Thin wrappers over ``jax.profiler`` so call sites never touch it directly:

  * :func:`annotate` — a ``TraceAnnotation`` context: the wrapped block
    shows up as a named span on the profiler's host timeline, on the same
    clock as the device's operations, with integer keyword ``stats``
    attached to the event (``annotate("run_mesh/sync", reads=9)``).
  * :func:`step` — the same over ``StepTraceAnnotation``: one iteration of
    a loop, carrying its ``step_num``.

Spans cost about a microsecond each when no profiler session is running
and change nothing the program computes. ``fed.run_mesh`` and
``train.trainer.train`` open them at their phase boundaries (the names are
listed in docs/observability.md); the benchmark's readers find them by name
in a traced window.

For *in-graph* attribution the dispatch layer wraps every Pallas tree
kernel in ``jax.named_scope`` (``kernels/<name>`` — see
``kernels/ops._dispatch``) and the composed step in ``chb_step[<backend>]``;
that is HLO metadata only.
"""
from __future__ import annotations

import jax

#: Re-export: the in-graph (HLO metadata) scope used by the dispatch layer.
named_scope = jax.named_scope


def annotate(name: str, **stats: int) -> jax.profiler.TraceAnnotation:
    """A named host span; ``stats`` are ints known when it opens."""
    return jax.profiler.TraceAnnotation(name, **stats)


def step(name: str, step_num: int) -> jax.profiler.StepTraceAnnotation:
    """A named host span for one loop iteration, numbered ``step_num``."""
    return jax.profiler.StepTraceAnnotation(name, step_num=step_num)
