"""The control (the reference in the program's place, counts held in
int16) fails each cell's comparison at a size whose counts pass 2^15;
and the benchmark's entry behaves without a chip or without the
program."""

from __future__ import annotations

import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
from _bench_subproc import ROOT, bench  # noqa: E402

CONTROL = str(ROOT / "bench" / "control.py")
SIZES = {"wc_large.batch": ["windows=16384", "vocab=4096"],
         "wc_large.ingest": ["windows=16384", "vocab=4096"],
         "wc_large.shuffle_x4": ["windows=16384", "vocab=4096"],
         "hg_large.batch": ["pixels=8650752"]}


@pytest.mark.parametrize("cell", sorted(SIZES))
def test_control_is_not_correct(cell):
    args = ["--workload", cell, "--seed", "3", "--seed", str(2**31 + 3),
            "--snapshots", "4"]
    for kv in SIZES[cell]:
        args += ["--set", kv]
    proc = bench(args, script=CONTROL)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(x) for x in proc.stdout.strip().splitlines()]
    assert len(lines) == 2
    assert all(x["wrong_keys"] > 0 for x in lines)


def test_without_a_chip_no_result():
    proc = bench(["--workload", "wc_large.batch", "--seed", "1",
                  "--seconds", "1"])
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_without_the_program_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = bench(["--workload", "wc_large.batch", "--seed", "1",
                  "--seconds", "1", "--rehearse"],
                 script=str(tmp_path / "bench" / "run.py"), cwd=tmp_path)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_importing_the_benchmark_loads_no_tpu_library():
    code = (
        "import sys\n"
        f"sys.path[:0] = [{str(ROOT)!r}, {str(ROOT / 'src')!r}]\n"
        "from bench import harness, registry, trace_reduce, jobs\n"
        "import bench.control, bench.compile_rehearsal\n"
        "for name in [w['name'] for w in registry.benchmark()['workloads']]:\n"
        "    c = registry.cell(name)\n"
        "    for p in [c.app_path, c.reference_path, c.driver_path]"
        " + [c.metric_path(m['name']) for m in c.per_layer]:\n"
        "        registry.load_module(p)\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, xla_bridge._backends\n"
        "maps = open('/proc/self/maps').read()\n"
        "assert 'libtpu' not in maps, 'libtpu is loaded'\n"
        "print('ok')\n")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode == 0 and proc.stdout.strip() == "ok", proc.stderr
