"""The command refuses a CPU device, and a checkout without the program."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys

from bench import run
from bench.tests.tiny import ROOT, workloads


def test_command_refuses_a_cpu_device(capsys):
    rc = run.main(["--workload", workloads()[0], "--seed", "2147483659",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out.strip() == ""
    assert "no TPU" in out.err


def test_command_fails_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    # past the look for a chip, the run needs the program and finds none
    code = ("import sys; from bench import run; "
            f"print(run.run({workloads()[0]!r}, 3, 1.0, False, require_tpu=False))")
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "repro" in p.stderr
