"""host_bytes_per_req.serve on a small serve cell: the engine's own
counter of host-device bytes over the requests it retired, and nothing
where the program keeps no such counter."""
import jax

from chipbench import cells, run as run_mod
from chipbench.tests.small import SERVE, run, small_cell

NAME = "host_bytes_per_req.serve"


def test_reported_in_the_traced_serve_line():
    cell = small_cell(SERVE)
    out = run(cell, seconds=2.5, trace=True)
    line = run_mod.result_line(cell, out, jax.devices(), True)
    n = 1
    for side in cell.config["operator"]["grid"]:
        n *= side
    # a retired request's column comes back at least, 4 bytes a row
    assert line["metrics"][NAME]["value"] >= 4 * n
    assert line["metrics"][NAME]["unit"] == "B"


def test_nothing_to_read_without_the_counter(monkeypatch):
    from repro.observe import metrics

    cell = small_cell(SERVE)
    out = run(cell)
    snap = metrics.snapshot()
    snap.pop("repro_engine_host_bytes_total")
    monkeypatch.setattr(metrics, "snapshot", lambda: snap)
    assert cells.metric_reader(NAME)(out.record) is None
