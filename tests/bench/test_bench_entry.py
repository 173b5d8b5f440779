"""The measuring entry refuses to measure off the chip, and without the
program beside it, and prints no result then."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "codeqwen1.5-7b.code.kv-host", "--seed", "3",
        "--seconds", "1", "--trace", "0"]


def run_entry(cwd: Path):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "XLA_FLAGS")}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "bench/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def results(stdout: str):
    out = []
    for ln in stdout.splitlines():
        try:
            d = json.loads(ln)
        except ValueError:
            continue
        if isinstance(d, dict) and "metrics" in d:
            out.append(d)
    return out


def test_no_result_without_a_tpu(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for d in ("bench", "src"):
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = run_entry(tmp_path)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert results(p.stdout) == []
    assert not (tmp_path / ".bench_trace").exists()


def test_no_result_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for d in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = run_entry(tmp_path)
    assert p.returncode != 0
    assert "src/" in p.stderr
    assert results(p.stdout) == []
