"""Multi-device (8 virtual CPU devices) collective-schedule tests.

Each check runs in a SUBPROCESS so this pytest process keeps its 1-device
view (jax locks the device count at first init)."""
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(__file__)
CHECKS = [
    "tp_equiv",
    "train_grads",
    "zero1_multidev",
    "topk_sync",
    "one_shot_sync",
    "kv_seq_shard",
    "embed_modes",
    "engine_tp",
    "placed_init",
]


@pytest.mark.parametrize("check", CHECKS)
def test_distributed(check):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(HERE, "..", "src")
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "dist_checks.py"), check],
        capture_output=True, text=True, timeout=900, env=env,
    )
    assert r.returncode == 0, f"{check} failed:\n{r.stdout[-3000:]}\n{r.stderr[-3000:]}"
    assert f"PASS {check}" in r.stdout
