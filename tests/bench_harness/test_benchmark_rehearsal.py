"""A whole run of the benchmark on the CPU at a tiny size: the chip check
is satisfied inside the test, everything after it is the real harness
(orchestrator, served path, client, check). Also: no TPU, no result."""

import json

import pytest

from benchmark import run

from _tiny import CPU, PEAKS, SEED, tiny_cell

E2E = {"setup_s", "delivered_fps", "latency_p50_ms", "latency_p95_ms"}


def test_cpu_rehearsal_of_a_whole_run():
    out = run.run_cell(tiny_cell(), SEED, 2.0, False, CPU, PEAKS)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(out)[-1] == "checks"
    assert out["window_compiles"] == 0
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == E2E
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"
    assert {c: out["checks"][c]["limit"] for c in ("decode_errors", "lost_aus")} == {
        "decode_errors": 0, "lost_aus": 0}
    json.dumps(out)


def test_traced_run_reports_per_layer_metrics():
    out = run.run_cell(tiny_cell("h264-1080p60.game"), SEED + 1, 2.0, True, CPU, PEAKS)
    assert out["correct"] is True, out["checks"]
    metrics = set(out["metrics"])
    assert {"tick_lag_ms", "frontend_ms", "step_ms", "fetch_ms", "pack_ms",
            "device_idle_pct"} <= metrics
    assert 0 < out["device"]["busy_s"] < out["device"]["window_s"]
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert 0 < len(out["breakdown"]["device_ops"]) <= 10


def test_no_tpu_means_no_result(capsys, monkeypatch):
    monkeypatch.setattr(run, "CACHE_ENV", {})  # leave this process's env alone
    rc = run.main(["--workload", "h264-1080p60.game", "--seed", "1",
                   "--seconds", "1"])
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_unknown_device_kind_is_an_error():
    with pytest.raises(run.NoChip, match="not in benchmark/peaks.json"):
        run.load_peaks("cpu")
    assert run.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
