"""Run ``bench/run.py`` (or another benchmark script) in a CPU subprocess,
optionally with the program broken underneath by a patch."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import registry  # noqa: E402

def prelude(root: Path) -> str:
    """Load ``<root>/bench/run.py`` as ``run``, with the program's ``src``
    of this repository on the path."""
    return f"""
import importlib.util, sys
sys.path[:0] = [{str(root)!r}, {str(ROOT / 'src')!r}]
spec = importlib.util.spec_from_file_location(
    "bench_run_main", {str(root / 'bench' / 'run.py')!r})
run = importlib.util.module_from_spec(spec)
spec.loader.exec_module(run)
"""


def bench(args, *, devices: int = 1, patch: str = "", script: str = "",
          cwd: Path = ROOT, root: Path = ROOT, timeout: float = 600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    if devices > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    if script:
        cmd = [sys.executable, script, *args]
    else:
        cmd = [sys.executable, "-c",
               prelude(root) + patch
               + f"\nsys.exit(run.main({list(args)!r}))"]
    return subprocess.run(cmd, capture_output=True, text=True,
                          timeout=timeout, env=env, cwd=cwd)


def result_line(proc) -> dict:
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}
def check_line(cell: str, out: dict, trace: int) -> None:
    c = registry.cell(cell)
    assert set(out) == KEYS | ({"breakdown"} if trace else set())
    assert list(out)[-1] == "checks"
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1
    assert out["checks"]["wrong_keys"] == {"value": 0, "limit": 0}
    want = c.per_layer if trace else c.end_to_end
    # the roofline needs a chip's published peaks: a CPU has none
    names = {m["name"] for m in want if "roofline" not in m["name"]}
    assert names <= set(out["metrics"]) <= {m["name"] for m in want}
    for m in want:
        if m["name"] in out["metrics"]:
            assert out["metrics"][m["name"]]["unit"] == m["unit"]
    dev = out["device"]
    assert dev["platform"] == "cpu" and dev["count"] == c.chips
    if trace:
        assert 0 < dev["busy_s"] <= dev["window_s"]
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
        assert len(out["breakdown"]["device_ops"]) <= 10
