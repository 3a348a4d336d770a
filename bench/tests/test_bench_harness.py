"""The harness finds pieces by name and refuses what is not a TPU."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402


def test_benchmark_cells_resolve_to_their_files():
    bench = harness.load_benchmark()
    for w in bench["workloads"]:
        cell = harness.find_cell(w["name"], bench)
        assert cell.config["name"] == w["config"]
        assert harness.driver_for(cell).run
        assert "setup_s" in [m["name"] for m in cell.end_to_end]
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert harness.reader_for(m["name"]).read


def test_added_files_are_found_by_name(tmp_path):
    """A later change adds a configuration, a mix, a driver and a metric as
    files and entries, and edits nothing that is there."""
    for kind in ("configs", "traffic", "drivers", "metrics"):
        (tmp_path / kind).mkdir()
    (tmp_path / "configs" / "toy.json").write_text(json.dumps(
        {"name": "toy", "limits": {}}))
    (tmp_path / "configs" / "toy.task.py").write_text("ANSWER = 42\n")
    (tmp_path / "traffic" / "toy.mix.json").write_text(json.dumps(
        {"driver": "toy_driver"}))
    (tmp_path / "drivers" / "toy_driver.py").write_text(
        "def run(ctx):\n    return 'ran'\n")
    (tmp_path / "metrics" / "toy.metric.py").write_text(
        "def read(ctx, outcome, trace):\n    return 1.5\n")
    bench = {"workloads": [{"name": "toy.cell", "config": "toy",
                            "traffic": "toy.mix", "chips": 1}],
             "end_to_end": [{"name": "setup_s"},
                            {"name": "x", "workloads": ["other"]}],
             "per_layer": [{"name": "toy.metric", "workloads": ["toy.cell"]}]}
    cell = harness.find_cell("toy.cell", bench, bench_dir=tmp_path)
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]
    assert harness.driver_for(cell).run(None) == "ran"
    assert harness.config_part("toy", "task", tmp_path).ANSWER == 42
    assert harness.reader_for("toy.metric", tmp_path).read(None, None,
                                                           None) == 1.5
    with pytest.raises(harness.BenchError, match="no workload"):
        harness.find_cell("absent", bench, bench_dir=tmp_path)


def test_check_devices_refuses_the_cpu_and_names_it():
    with pytest.raises(harness.BenchError, match="needs a TPU; JAX found "
                       r"\d+ x cpu"):
        harness.check_devices(1)


def test_unknown_device_kind_is_an_error():
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.BenchError, match="not in bench/peaks.json"):
        harness.peaks_for("TPU v99")


def test_run_on_cpu_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "fed.emnist.full",
         "--seed", str(2 ** 31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_a_window_that_compiles_is_refused_and_names_the_program():
    import time

    import jax
    import jax.numpy as jnp

    def call(scale):
        def probe_window_program(x):
            return x * scale
        return jax.jit(probe_window_program)(jnp.ones(3))

    harness.configure_jax()     # the persistent cache serves a new jit
    counter = harness.CompileCounter()
    ctx = harness.Context(cell=None, seed=0, seconds=1.0, trace=False,
                          devices=jax.devices()[:1], counter=counter,
                          tracer=None)
    try:
        # the warm-up's program is what the window runs: nothing compiles
        scale = float(time.time_ns() % 10 ** 6)
        harness.entry_call(ctx, call, scale)
        out, _ = harness.entry_call(ctx, call, scale, window=True)
        assert float(out[0]) == scale
        # a constant the warm-up never saw makes a new program
        with pytest.raises(harness.BenchError,
                           match=r"1 program\(s\).*probe_window_program"):
            harness.entry_call(ctx, call, scale + 0.5, window=True)
    finally:
        counter.close()
