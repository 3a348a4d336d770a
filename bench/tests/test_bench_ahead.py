"""The reader of ``run_mesh/sync``'s ``ahead`` stat (``ahead_share.fed``), on
hand-made events and on a traced CPU run of the fed driver."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness, tracing  # noqa: E402

HOST = "/host:CPU"


def _ev(name, start, dur, **stats):
    return tracing.Event(HOST, "python3", name, start, dur, stats)


def _read(red):
    return harness.reader_for("ahead_share.fed").read(None, None, red)


def test_share_of_syncs_that_opened_ahead_of_the_device():
    red = tracing.Reduction([
        _ev(tracing.WINDOW, 0, 1000),
        _ev("run_mesh/sync", 100, 50, round=0, reads=9, fetches=1, ahead=1),
        _ev("run_mesh/sync", 300, 50, round=1, reads=9, fetches=1, ahead=0),
        _ev("run_mesh/sync", 500, 50, round=2, reads=9, fetches=1, ahead=1),
        _ev("run_mesh/sync", 700, 50, round=3, reads=9, fetches=1, ahead=1),
        # wholly outside the window: not read
        _ev("run_mesh/sync", 1100, 50, round=4, reads=9, fetches=1, ahead=0),
    ])
    assert _read(red) == pytest.approx(75.0)


def test_syncs_without_the_stat_give_nothing():
    """A program whose syncs read back serially carries no ``ahead``."""
    red = tracing.Reduction([
        _ev(tracing.WINDOW, 0, 1000),
        _ev("run_mesh/sync", 100, 50, reads=9),
        _ev("run_mesh/sync", 300, 50, reads=9),
    ])
    assert _read(red) is None
    assert _read(tracing.Reduction([_ev(tracing.WINDOW, 0, 1000)])) is None


def test_traced_cpu_fed_run_reads_the_share():
    from test_bench_spans import FED_SMALL, _traced_cpu_run

    out, red = _traced_cpu_run("fed.emnist.full", FED_SMALL)
    share = _read(red)
    assert share is not None and 0.0 <= share <= 100.0
