"""A CPU rehearsal of a whole run at a tiny size: every metric name comes
out and the program agrees with the reference. The values are CPU values
and are never printed under a device metric's name."""
import pytest

from bench import harness
from bench.tests import tiny
from bench.tests.test_bench_catalog import METHODS


@pytest.mark.parametrize("trace", [False, True])
def test_rehearsal_yields_every_metric(tmp_path, trace):
    cat = tiny.make_bench(str(tmp_path))
    res = harness.run_cell(tiny.CELL, 2**31 + 11, 0.3, trace, catalog=cat,
                           require_chip=False, log=lambda s: None)
    kind = "per_layer" if trace else "end_to_end"
    assert set(res["metrics"]) == {m["name"] for m in cat.benchmark[kind]}
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 2
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == set(tiny.LIMITS) | {"compiles_in_window"}
    assert res["checks"]["compiles_in_window"]["value"] == 0
    if trace:
        assert res["device"]["busy_s"] > 0
        assert res["device"]["busy_s"] <= res["device"]["window_s"]
        assert res["breakdown"]["device_ops"]
        assert res["breakdown"]["idle_gaps"]


@pytest.mark.parametrize("method", METHODS)
def test_the_reference_follows_every_method(tmp_path, method):
    cat = tiny.make_bench(str(tmp_path), method)
    res = harness.run_cell(f"tiny.{method}", 17, 0.1, False, catalog=cat,
                           require_chip=False, log=lambda s: None)
    assert res["correct"] is True, res["checks"]
