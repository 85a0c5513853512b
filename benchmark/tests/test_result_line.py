"""The last line of a run: its keys and types, for a passing and a failing
run, through the real command at the rehearsal size on the CPU backend."""

import json
import os
import subprocess
import sys

from benchmark import spec

RUN = [sys.executable, os.path.join(spec.HERE, "run.py"), "--workload", "rehearsal-test",
       "--config", "rehearsal-tiny", "--traffic", "rehearsal-closed", "--rehearse",
       "--seconds", "2", "--trace", "0"]


def _run(*extra):
    p = subprocess.run(RUN + list(extra), capture_output=True, text=True, timeout=280,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    return p.returncode, p.stdout.strip().splitlines()


def _check_shape(r):
    assert set(r) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert isinstance(r["correct"], bool)
    assert isinstance(r["attempted"], int) and isinstance(r["failed"], int)
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(r["device"]) <= {
        "platform", "kind", "count", "memory_peak_bytes", "busy_s", "window_s"}
    assert isinstance(r["device"]["count"], int)
    assert isinstance(r["device"]["memory_peak_bytes"], int)
    for m in r["metrics"].values():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float)


def test_a_passing_run_prints_the_contracts_line_last():
    rc, lines = _run("--seed", str(2**31 + 11))
    r = json.loads(lines[-1])
    _check_shape(r)
    assert rc == 0 and r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    e2e = {m["name"]: m["unit"] for m in spec.load_benchmark()["end_to_end"]}
    assert {k: v["unit"] for k, v in r["metrics"].items()} == e2e
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert any("rms_centred_logit_error=" in ln and "limit=" in ln for ln in lines), \
        "every run prints the number compared beside its limit"
    assert not os.path.exists(os.path.join(spec.REPO, ".benchmark_work", "rehearsal-test"))


def test_a_run_over_its_budget_prints_a_failing_line_and_exits():
    rc, lines = _run("--seed", "5", "--budget-s", "5")
    r = json.loads(lines[-1])
    _check_shape(r)
    assert rc == 1 and r["correct"] is False and r["metrics"] == {}
    assert any("OverBudget" in ln for ln in lines)
