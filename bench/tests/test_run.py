"""The entry point refuses to run anywhere but on the chip."""
import os
import shutil
import subprocess
import sys

from conftest import BENCH, ROOT


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "smollm-decode-a16", "--seed",
         str(2**31 + 3), "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_every_cell_and_metric_has_its_files(bench_json):
    for w in bench_json["workloads"]:
        for part in (f"configs/{w['config']}.json", f"configs/{w['config']}.py",
                     f"traffic/{w['traffic']}.json", f"cells/{w['name']}.json"):
            assert (BENCH / part).is_file(), part
    for m in bench_json["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file(), m["name"]
