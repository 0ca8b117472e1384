"""bench.py harness: `python bench.py` measures on the TPU and fails
without one (no CPU fallback, no carried-over result); `--smoke` is the
CPU variant whose round-over-round regression checks are tested here.
Reference for the metric shape: docs/benchmarks.rst:32-43."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def bench():
    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_peak_bf16_tflops_raises_on_unknown_kind(bench):
    """A device the table does not list is an error, not a 0.0 peak
    and a null MFU."""
    class Dev:
        device_kind = "TPU v5 lite"
    assert bench.peak_bf16_tflops(Dev()) == 197.0
    Dev.device_kind = "cpu"
    with pytest.raises(ValueError, match="device_kind 'cpu'"):
        bench.peak_bf16_tflops(Dev())


def test_bench_without_smoke_fails_off_the_tpu():
    """The measurement path finds no chip and fails; it neither falls
    back to the CPU nor prints a result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--only",
         "resnet"], env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "platform 'cpu'" in r.stderr
    assert r.stdout.strip() == ""


def test_lane_errors_fail_the_run(bench):
    """Lanes catch their own exceptions so the rest still report; any
    recorded error, nested or not, is what main() exits non-zero on."""
    out = {"device": {"platform": "tpu"},
           "resnet50": {"images_per_sec": 1.0, "profile": {"error": "x"}},
           "bert_large": {"error": "OOM"},
           "allreduce_eager": {"xla_control_1mb": {"error": None}}}
    assert sorted(bench._lane_errors(out)) == ["bert_large",
                                               "resnet50/profile"]
    assert bench._lane_errors({"serve": {"ok": True}}) == []


def test_smoke_regression_warns_beyond_spread(bench, tmp_path, capsys):
    """The CPU smoke headline must be compared against the prior
    round's artifact and flagged when it drops beyond the larger run's
    own spread_pct (round-5: a 13% smoke regression shipped silently)."""
    # Driver-wrapper artifact with a tail-embedded (front-truncated)
    # bench JSON — the shape real BENCH_r*.json files have.
    (tmp_path / "BENCH_r07.json").write_text(json.dumps({
        "n": 7, "rc": 0, "parsed": None,
        "tail": '..."resnet18_smoke": {"images_per_sec": 30.0, '
                '"batch_size": 8, "spread_pct": 6.0}, "other": 1}'}))
    out = {"resnet18_smoke": {"images_per_sec": 20.0,
                              "spread_pct": 4.0}}
    bench.check_smoke_regression(out, str(tmp_path))
    cmp = out["smoke_vs_prior"]
    assert cmp["regressed"] is True
    assert cmp["prior_source"] == "BENCH_r07.json"
    assert cmp["tolerance_pct"] == 6.0      # the larger spread wins
    assert "regressed" in capsys.readouterr().err

    # Within the noise band: recorded, not flagged.
    out = {"resnet18_smoke": {"images_per_sec": 28.8,
                              "spread_pct": 4.0}}
    bench.check_smoke_regression(out, str(tmp_path))
    assert out["smoke_vs_prior"]["regressed"] is False

    # Improvements never warn.
    out = {"resnet18_smoke": {"images_per_sec": 40.0,
                              "spread_pct": 4.0}}
    bench.check_smoke_regression(out, str(tmp_path))
    assert out["smoke_vs_prior"]["regressed"] is False


def test_smoke_regression_without_prior_is_silent(bench, tmp_path):
    out = {"resnet18_smoke": {"images_per_sec": 20.0}}
    bench.check_smoke_regression(out, str(tmp_path))
    assert "smoke_vs_prior" not in out


def test_smoke_regression_parses_parsed_artifact(bench, tmp_path):
    (tmp_path / "BENCH_r03.json").write_text(json.dumps({
        "rc": 0, "tail": "",
        "parsed": {"resnet18_smoke": {"images_per_sec": 25.0,
                                      "spread_pct": 3.0}}}))
    out = {"resnet18_smoke": {"images_per_sec": 26.0,
                              "spread_pct": 2.0}}
    bench.check_smoke_regression(out, str(tmp_path))
    assert out["smoke_vs_prior"]["prior_images_per_sec"] == 25.0


def test_smoke_regression_skips_zero_headline_prior(bench, tmp_path):
    """A failed prior smoke (images_per_sec 0) must be skipped as a
    baseline, via both the regex and dict paths — never divided by."""
    (tmp_path / "BENCH_r04.json").write_text(json.dumps({
        "rc": 1, "parsed": None,
        "tail": '..."resnet18_smoke": {"images_per_sec": 0.0, '
                '"spread_pct": 0.0}...'}))
    out = {"resnet18_smoke": {"images_per_sec": 20.0,
                              "spread_pct": 4.0}}
    bench.check_smoke_regression(out, str(tmp_path))
    assert "smoke_vs_prior" not in out
    # An older GOOD round behind the failed one is still found.
    (tmp_path / "BENCH_r03.json").write_text(json.dumps({
        "rc": 0, "tail": "",
        "parsed": {"resnet18_smoke": {"images_per_sec": 25.0,
                                      "spread_pct": 3.0}}}))
    bench.check_smoke_regression(out, str(tmp_path))
    assert out["smoke_vs_prior"]["prior_images_per_sec"] == 25.0


def test_dlrm_regression_warns_and_records_ratio(bench, tmp_path,
                                                 capsys):
    prior = {"dlrm_tiny": {"steps_per_sec": 20.0,
                           "steps_per_sec_spread": [19.0, 21.0],
                           "checkpoint": {
                               "delta_vs_full_bytes_ratio": 0.02}}}
    with open(tmp_path / "BENCH_r07.json", "w") as f:
        json.dump(prior, f)
    out = {"dlrm_tiny": {"steps_per_sec": 10.0,
                         "steps_per_sec_spread": [9.5, 10.5],
                         "checkpoint": {
                             "delta_vs_full_bytes_ratio": 0.03}}}
    bench.check_dlrm_regression(out, str(tmp_path))
    cmp = out["dlrm_vs_prior"]
    assert cmp["regressed"] is True
    assert cmp["prior_source"] == "BENCH_r07.json"
    assert cmp["delta_vs_full_bytes_ratio"] == 0.03
    assert "DLRM lane regressed" in capsys.readouterr().err


def test_dlrm_regression_without_prior_records_ratio_only(bench,
                                                          tmp_path):
    out = {"dlrm_tiny": {"steps_per_sec": 10.0,
                         "checkpoint": {
                             "delta_vs_full_bytes_ratio": 0.02}}}
    bench.check_dlrm_regression(out, str(tmp_path))
    assert out["dlrm_vs_prior"] == {"delta_vs_full_bytes_ratio": 0.02}


def test_dlrm_regression_warns_on_ratio_above_target(bench, tmp_path,
                                                     capsys):
    out = {"dlrm_tiny": {"steps_per_sec": 10.0,
                         "checkpoint": {
                             "delta_vs_full_bytes_ratio": 0.4}}}
    bench.check_dlrm_regression(out, str(tmp_path))
    assert "exceeds the 0.1" in capsys.readouterr().err


def test_dlrm_regression_inside_noise_is_silent(bench, tmp_path,
                                                capsys):
    prior = {"dlrm_tiny": {"steps_per_sec": 10.5,
                           "steps_per_sec_spread": [10.0, 11.0]}}
    with open(tmp_path / "BENCH_r07.json", "w") as f:
        json.dump(prior, f)
    out = {"dlrm_tiny": {"steps_per_sec": 10.0,
                         "steps_per_sec_spread": [9.8, 10.2]}}
    bench.check_dlrm_regression(out, str(tmp_path))
    assert out["dlrm_vs_prior"]["regressed"] is False
    assert "regressed" not in capsys.readouterr().err
