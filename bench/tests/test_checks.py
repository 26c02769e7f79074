"""The comparison that decides ``correct``, driven through the rest of a
run on the CPU at a tiny size: a sound run passes, the control (the
reference one precision lower in the program's place) fails."""

import jax
import pytest

from bench.tests import tiny

CELLS = ["xlstm_350m.local", "minicpm_2b.local"]


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload, monkeypatch):
    tiny.no_cache(monkeypatch)
    res = tiny.run(tiny.cell(workload), jax.devices()[:1])
    assert res.correct, res.check_lines()
    line = res.line()
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {"local_samples_per_s", "setup_s"}
    assert line["attempted"] >= 1 and line["failed"] == 0


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload, monkeypatch):
    tiny.no_cache(monkeypatch)
    res = tiny.run(tiny.cell(workload), jax.devices()[:1], control=True)
    assert not res.correct, res.check_lines()


def test_traced_run_reports_per_layer_metrics(monkeypatch):
    tiny.no_cache(monkeypatch)
    res = tiny.run(tiny.cell("xlstm_350m.local"), jax.devices()[:1],
                   trace=True)
    line = res.line()
    # the CPU has no device planes: only what needs no device trace is read
    assert "local_mfu" in line["metrics"]
    assert "forward_ms" not in line["metrics"]
    assert line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
