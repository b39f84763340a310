"""The harness's arithmetic: latency from the due time, the rate over the
whole window, failures counted, and no result without a TPU."""
import os
import pathlib
import subprocess
import sys

import numpy as np

import harness
import measures


def rec(due, done=None, shed=False, submit=None):
    r = harness.Rec(due)
    r.t_done, r.shed = done, shed
    r.t_submit = due if submit is None else submit
    return r


def test_latency_runs_from_due_time_not_submit():
    r = rec(10.0, done=10.050, submit=10.040)
    assert np.allclose(measures.latencies_ms([r]), [50.0])
    assert np.allclose(measures.lateness_ms([r]), [40.0])


def test_percentiles_over_all_requests():
    recs = [rec(0.0, done=i / 1000) for i in range(1, 101)]
    lat = measures.latencies_ms(recs)
    assert measures.percentile(lat, 50) == np.percentile(np.arange(1, 101), 50)
    assert measures.percentile(lat, 95) == np.percentile(np.arange(1, 101), 95)
    assert measures.percentile(np.array([]), 95) is None


def test_failed_requests_wait_until_the_run_gave_up():
    recs = [rec(0.0, done=0.010), rec(0.5)]
    assert np.allclose(measures.latencies_ms(recs, gave_up=2.0),
                       [10.0, 1500.0])


def test_rate_counts_answers_inside_the_window_only():
    recs = [rec(0.0, done=t) for t in (0.5, 1.0, 9.9, 10.0, 10.5)]
    recs.append(rec(1.0))                        # never answered
    assert measures.answered_in_window(recs, t0=1.0, seconds=9.0) == 3
    assert measures.rate_per_s(recs, t0=1.0, seconds=9.0) == 3 / 9.0


def test_shed_and_unanswered_count_as_failed():
    recs = [rec(0.0, done=0.1), rec(0.0, shed=True), rec(0.0)]
    assert measures.failed(recs) == 2


def test_command_exits_nonzero_without_a_tpu():
    root = pathlib.Path(__file__).resolve().parents[2]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "elite-fp32.backlog", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=root, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_a_declared_metric_that_reads_nothing_fails_the_run(monkeypatch,
                                                            capsys):
    """A silent kernel roofline is let through (a change may take the
    kernel off the path); any other silent metric ends the run with no
    result line."""
    import run

    def fake_run_cell(cell, *a, **kw):
        window = harness.Window(0.0, 1.0, [], False, {}, {}, 0)
        return {"out": {"correct": True}, "checks": [], "window": window,
                "lateness_ms": np.array([]), "silent": silent}

    monkeypatch.setattr(harness, "require_chips", lambda chips: [None])
    monkeypatch.setattr(run, "run_cell", fake_run_cell)
    argv = ["--workload", "elite-fp32.backlog", "--seed", "1",
            "--seconds", "1", "--trace", "1"]
    silent = ["cbr_roofline.backlog"]
    assert run.main(argv) == 0
    assert capsys.readouterr().out.strip().startswith("{")
    silent = ["cbr_roofline.backlog", "step_mfu.backlog"]
    assert run.main(argv) == 3
    captured = capsys.readouterr()
    assert captured.out.strip() == ""
    assert "step_mfu.backlog read nothing" in captured.err
