"""Without a TPU the command fails and prints no result: a CPU number never
appears under a device metric's name."""

import os
import subprocess
import sys

from conftest import ROOT


def test_run_fails_without_a_tpu_and_names_the_device():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "higgs.fused-sort",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "'platform': 'cpu'" in p.stderr
