"""Perf-regression gate (tools/bench_gate.py): the committed BENCH
history passes its own thresholds, an injected regression fails
loudly, direction heuristics gate throughput down / latency up,
brand-new metrics are not gated, and a bench_serve artifact's own
failed bars outrank any margin."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools import bench_gate  # noqa: E402


def _art(path, metrics, bars=None):
    payload = {"metrics": metrics}
    if bars is not None:
        payload["bars_failed"] = bars
    path.write_text(json.dumps(payload))
    return str(path)


def test_committed_history_passes_and_smoke_contract():
    """The repo's own BENCH artifacts must pass the gate (the ci_check
    stage-10 precondition), and the full --smoke contract holds:
    history green, 2x-degraded artifact caught — also for a family
    that holds a single artifact."""
    history = bench_gate.default_history()
    assert len(history) >= 2
    assert bench_gate.gate(history, history[-1]) == 0
    assert bench_gate.smoke(history) == 0


def test_gate_fails_on_degraded_artifact(tmp_path):
    history = bench_gate.default_history()
    degraded = str(tmp_path / os.path.basename(history[-1]))
    bench_gate.degrade(history[-1], degraded)
    assert bench_gate.gate(history, degraded) == 1


def test_direction_heuristics():
    d = bench_gate.direction
    assert d("resnet50_images_per_sec_per_chip", "images/sec/chip") == \
        "higher"
    assert d("lm_tokens_per_sec_per_chip", "tokens/sec/chip") == "higher"
    assert d("serve_latency_p99", "s") == "lower"
    assert d("serve_decode_gap_s_p99", "s") == "lower"
    assert d("router_affinity_hits_total", "requests") == "higher"
    assert d("mystery_metric", "widgets") is None


def test_noise_band_uses_recorded_spread(tmp_path):
    """A metric whose history shows wide value_min/value_max spread
    gets a proportionally wide band; a tight metric gets the floor."""
    old = _art(tmp_path / "BENCH_a.json", [
        {"metric": "tight_per_sec", "value": 100.0, "value_min": 99.0,
         "value_max": 101.0, "unit": "images/sec"},
        {"metric": "noisy_per_sec", "value": 100.0, "value_min": 70.0,
         "value_max": 130.0, "unit": "images/sec"}])
    # -10%: outside the tight metric's floor band, inside the noisy
    # metric's 2x-spread band
    new = _art(tmp_path / "BENCH_b.json", [
        {"metric": "tight_per_sec", "value": 90.0, "unit": "images/sec"},
        {"metric": "noisy_per_sec", "value": 90.0, "unit": "images/sec"}])
    rc = bench_gate.gate([old], new)
    assert rc == 1
    # the same -10% on ONLY the noisy metric passes
    new2 = _art(tmp_path / "BENCH_c.json", [
        {"metric": "tight_per_sec", "value": 99.5,
         "unit": "images/sec"},
        {"metric": "noisy_per_sec", "value": 90.0,
         "unit": "images/sec"}])
    assert bench_gate.gate([old], new2) == 0


def test_latency_gates_upward_and_new_metric_ungated(tmp_path):
    old = _art(tmp_path / "BENCH_a.json", [
        {"metric": "serve_latency_p99", "value": 1.0, "unit": "s"}])
    worse = _art(tmp_path / "BENCH_b.json", [
        {"metric": "serve_latency_p99", "value": 2.0, "unit": "s"},
        {"metric": "brand_new_per_sec", "value": 5.0,
         "unit": "tokens/sec"}])
    assert bench_gate.gate([old], worse) == 1
    better = _art(tmp_path / "BENCH_c.json", [
        {"metric": "serve_latency_p99", "value": 0.5, "unit": "s"}])
    assert bench_gate.gate([old], better) == 0


def test_families_gate_independently(tmp_path):
    """Once a BENCH_serve artifact is committed, the default/smoke
    modes must STILL gate the training family — newest-of-each-family,
    not lexicographic newest overall (BENCH_serve* sorts after every
    BENCH_r*)."""
    r1 = _art(tmp_path / "BENCH_r01.json", [
        {"metric": "train_per_sec", "value": 100.0,
         "unit": "images/sec"}])
    r2 = _art(tmp_path / "BENCH_r02.json", [
        {"metric": "train_per_sec", "value": 50.0,
         "unit": "images/sec"}])     # a real training regression
    s1 = _art(tmp_path / "BENCH_serve_r01.json", [
        {"metric": "serve_tokens_per_sec", "value": 40.0,
         "unit": "tokens/sec"}])
    s2 = _art(tmp_path / "BENCH_serve_r02.json", [
        {"metric": "serve_tokens_per_sec", "value": 41.0,
         "unit": "tokens/sec"}])
    history = [r1, r2, s1, s2]
    fams = bench_gate.families(history)
    assert fams == {"train": [r1, r2], "serve": [s1, s2]}
    # default mode (main with no candidate) must catch the regressed
    # TRAINING artifact even though the serve family is green
    assert bench_gate.main(["--history", *history]) == 1
    # with a healthy training family, both families pass
    r2_ok = _art(tmp_path / "BENCH_r02.json", [
        {"metric": "train_per_sec", "value": 101.0,
         "unit": "images/sec"}])
    assert bench_gate.main(["--history", r1, r2_ok, s1, s2]) == 0
    # smoke gates each family's own degraded copy
    assert bench_gate.smoke([r1, r2_ok, s1, s2]) == 0


def test_serve_bars_failed_fails_outright(tmp_path):
    old = _art(tmp_path / "BENCH_serve_a.json", [
        {"metric": "serve_tokens_per_sec", "value": 50.0,
         "unit": "tokens/sec"}])
    bad = _art(tmp_path / "BENCH_serve_b.json", [
        {"metric": "serve_tokens_per_sec", "value": 55.0,
         "unit": "tokens/sec"}], bars=["prefix_sharing_concurrency"])
    assert bench_gate.gate([old], bad) == 1
    ok = _art(tmp_path / "BENCH_serve_c.json", [
        {"metric": "serve_tokens_per_sec", "value": 55.0,
         "unit": "tokens/sec"}], bars=[])
    assert bench_gate.gate([old], ok) == 0


def test_no_history_and_no_metrics_are_loud(tmp_path):
    lone = _art(tmp_path / "BENCH_a.json", [
        {"metric": "x_per_sec", "value": 1.0, "unit": "images/sec"}])
    assert bench_gate.gate([lone], lone) == 2
    empty = tmp_path / "BENCH_empty.json"
    empty.write_text("{}")
    assert bench_gate.gate([lone], str(empty)) == 2


def test_wrapped_parsed_artifacts_extract_nested_metrics(tmp_path):
    """A driver-written {"parsed": ...} wrapper with nested lm /
    input_pipeline sub-benches extracts everything, first occurrence
    wins (input_pipeline's "default" arm does not clobber the
    headline)."""
    wrapped = tmp_path / "BENCH_r01.json"
    wrapped.write_text(json.dumps({"n": 1, "rc": 0, "parsed": {
        "metric": "resnet50_images_per_sec_per_chip", "value": 2000.0,
        "value_min": 1990.0, "value_max": 2010.0,
        "unit": "images/sec/chip",
        "input_pipeline": {
            "metric": "imagenet_input_pipeline_images_per_sec_per_host",
            "value": 277.6, "value_min": 191.1,
            "unit": "images/sec/host",
            "default": {
                "metric":
                    "imagenet_input_pipeline_images_per_sec_per_host",
                "value": 285.7, "unit": "images/sec/host"}},
        "lm": {"metric": "lm_tokens_per_sec_per_chip",
               "value": 100000.0, "tps_min": 99000.0,
               "tps_max": 101000.0, "unit": "tokens/sec/chip"}}}))
    metrics, bars = bench_gate.load_artifact(str(wrapped))
    assert "resnet50_images_per_sec_per_chip" in metrics
    assert "lm_tokens_per_sec_per_chip" in metrics
    assert "imagenet_input_pipeline_images_per_sec_per_host" in metrics
    assert metrics["imagenet_input_pipeline_images_per_sec_per_host"][
        "value"] == pytest.approx(277.6)
    assert bars == []
    # the lm sub-bench's tps_min/tps_max count as spread
    assert metrics["lm_tokens_per_sec_per_chip"]["spread"] is not None
