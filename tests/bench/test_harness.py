"""The harness's host logic, rehearsed on the CPU at a tiny size: a sound
run comes out correct, each fault planted in the timed path comes out not
correct, and a run without a TPU or without the kernels is refused."""
import os
import subprocess
import sys

import pytest

import rehearsal
from bench import e2e, harness, report
from bench.spec import ROOT

SEED = 2**31 + 77
LIMIT = rehearsal.CELL["mean_logit_gap_limit"]


def test_sound_run_is_correct(monkeypatch):
    raw = rehearsal.run(monkeypatch, SEED)
    line = report.result(raw, trace=False)
    assert line["correct"] is True
    assert list(line)[-1] == "compared"
    assert line["compared"]["mean_logit_gap"]["value"] <= LIMIT
    assert line["compared"]["tokens_checked"]["value"] >= harness.CHECK_TOKENS
    assert raw["check"]["slots_checked"] == rehearsal.CELL["n_slots"]
    assert set(line["metrics"]) == {"output_tokens_per_s", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert raw["compiles_in_window"] == 0


def test_poisson_run_reports_its_tails(monkeypatch):
    raw = rehearsal.run(monkeypatch, SEED, mix=rehearsal.POISSON)
    line = report.result(raw, trace=False)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"output_tokens_per_s", "setup_s"}
    assert len(e2e.itl_samples(raw["logs"], raw["window"])) > 0
    due = [g for g in raw["logs"] if g.due is not None
           and raw["window"][0] <= g.due < raw["window"][1]]
    assert len(due) == round(rehearsal.CELL["rate_per_s"] * 2.0)
    assert len(e2e.ttft_samples(raw["logs"], raw["window"])) == len(due)


def test_traced_run_profiles_whole_steps(monkeypatch):
    raw = rehearsal.run(monkeypatch, SEED, trace=True)
    assert report.result(raw, trace=True)["correct"] is True
    traced = [r for r in raw["records"] if r.traced]
    assert traced and raw["span"][0] <= traced[0].t_start
    assert traced[-1].t_end <= raw["span"][1]
    assert raw["trace"] is None  # the CPU backend has no device plane


@pytest.mark.parametrize("fault", rehearsal.FAULTS)
def test_fault_in_the_timed_path_is_not_correct(monkeypatch, fault):
    raw = rehearsal.run(monkeypatch, SEED, fault=fault)
    line = report.result(raw, trace=False)
    assert line["correct"] is False
    assert line["compared"]["mean_logit_gap"]["value"] > LIMIT


@pytest.mark.parametrize("fault", rehearsal.FAULTS)
def test_poisson_fault_is_not_correct(monkeypatch, fault):
    raw = rehearsal.run(monkeypatch, SEED, mix=rehearsal.POISSON,
                        fault=fault)
    line = report.result(raw, trace=False)
    assert line["correct"] is False
    assert line["compared"]["mean_logit_gap"]["value"] > LIMIT


def test_refuses_an_engine_without_the_kernels(monkeypatch):
    monkeypatch.setattr(harness, "program_config", rehearsal.tiny_cfg)
    with pytest.raises(RuntimeError, match="fast_kernels"):
        harness.run_cell(rehearsal.tiny_spec(), SEED, 1.0, False,
                         t_start=0.0)


def test_refuses_a_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "opt-1.3b.decode-batch", "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr
