"""The command's behaviour off the chip: no result without an accelerator
or without the program, and no device metric from a CPU run."""

import json
import os
import subprocess
import sys

from helpers import ROOT, copy_bench, run

ARGS = ["--workload", "paper.digits.backlog", "--seed", "4294967311",
        "--seconds", "1", "--trace", "0"]


def _cmd(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chipbench/run.py", *ARGS],
                          cwd=root, env=env, capture_output=True, text=True,
                          timeout=300)


def _printed_a_result(stdout):
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return True
        except (json.JSONDecodeError, TypeError):
            continue
    return False


def test_no_accelerator_no_result():
    p = _cmd(ROOT)
    assert p.returncode != 0
    assert not _printed_a_result(p.stdout)
    assert "no accelerator" in p.stderr


def test_checkout_without_the_program_no_result(tmp_path):
    p = _cmd(copy_bench(tmp_path))
    assert p.returncode != 0
    assert not _printed_a_result(p.stdout)


def test_cpu_trace_run_has_no_device_metric():
    line, _ = run("paper.digits.backlog", seconds=0.3, trace=True)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"engine_round_ms.backlog",
                                    "lane_step_yield"}
    assert "busy_s" not in line["device"] and "breakdown" not in line
    assert list(line)[-1] == "checks"
