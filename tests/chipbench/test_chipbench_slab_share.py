"""The ``lscd_slab_share`` reader: it finds the program's gauge through the
harness's own counter snapshots, with no edit to the harness, and reads
nothing where the program has no such gauge."""

from __future__ import annotations

import pytest

import chipbench_tiny  # puts the program on the path
from chipbench import harness
from repro.core import tiled_csl
from repro.serving.scheduler import SchedulerMetrics


def _run(open_metrics, close_metrics, drop=()):
    def snap(t, metrics):
        counters = harness.counters(metrics)
        for name in drop:
            counters.pop(name)
        return {"t": t, "counters": counters}
    return {"origin": 10.0, "requests": [], "end_t": 11.0, "steps": [],
            "lateness": [], "queue": {}, "open": snap(10.0, open_metrics),
            "close": snap(11.0, close_metrics)}


def test_reader_takes_the_gauge_at_the_windows_close():
    cell = chipbench_tiny.cell("gelu")
    rec = harness.window_record(cell, _run(
        SchedulerMetrics(lscd_slab_share=72.5, steps=3),
        SchedulerMetrics(lscd_slab_share=72.5, steps=9)))
    assert rec["delta"]["lscd_slab_share"] == 0.0     # a gauge: no change
    assert harness.load_reader("lscd_slab_share")(rec) == 72.5


@pytest.mark.parametrize("drop", [("lscd_slab_share",), ()])
def test_reader_reads_nothing_without_the_gauge(drop):
    """A program without the gauge (``drop``), or one that serves no
    Tiled-CSL weight (the gauge left at 0), gives no reading."""
    cell = chipbench_tiny.cell("gelu")
    rec = harness.window_record(cell, _run(SchedulerMetrics(),
                                           SchedulerMetrics(), drop))
    assert harness.load_reader("lscd_slab_share")(rec) is None


def test_tiny_cell_server_reports_its_trees_slab_share(tmp_path,
                                                      monkeypatch):
    monkeypatch.setattr(harness, "CACHE", str(tmp_path / "cache"))
    cell = chipbench_tiny.cell("gelu")
    cfg = harness.model_config(cell)
    params = harness.served_params(cell, cfg, {})
    server = harness.make_server(params, cfg, cell)
    snap = harness.counters(server.metrics)
    rec = {"counters": {"open": snap, "close": snap}}
    got = harness.load_reader("lscd_slab_share")(rec)
    assert got == pytest.approx(tiled_csl.slab_share(params))
    assert 0.0 < got <= 100.0
