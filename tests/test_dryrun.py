"""Dry-run integration: the lowering path works end-to-end on a small
forced-device mesh in a subprocess (the 512-device production matrices
are exercised offline).
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys
from repro.configs import load_all
from repro.launch import mesh as mesh_lib, dryrun
load_all()
mesh = mesh_lib.make_debug_mesh((2, 4), ("data", "model"))
out = [dryrun.run_one(a, s, mesh=mesh, verbose=False)
       for a, s in [("mixtral-8x7b", "train_4k")]]
print(json.dumps(out))
"""


@pytest.mark.slow
def test_dryrun_small_mesh_subprocess():
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-2000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    for r in res:
        assert r["status"] == "ok", r
        assert r["cost"].get("flops", 0) > 0
        assert r["memory"]["peak_bytes"] > 0

