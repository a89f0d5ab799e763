"""The command line refuses, with a non-zero exit and no result line,
what it cannot measure: no TPU, a kernel mode other than compiled
kernels, and a checkout that holds only the benchmark."""
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run_cli(cwd, env_extra):
    env = {"PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "HOME": os.path.expanduser("~"),
           "JAX_PLATFORMS": "cpu", **env_extra}
    return subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", "testbed-retry_storm",
         "--seed", str(2**33 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def assert_refused(out):
    assert out.returncode != 0, out.stdout + out.stderr
    assert not any(line.lstrip().startswith("{")
                   for line in out.stdout.splitlines()), out.stdout


def test_no_tpu_is_refused():
    out = run_cli(ROOT, {})
    assert_refused(out)
    assert "no TPU" in out.stderr


def test_kernel_mode_other_than_compiled_is_refused():
    out = run_cli(ROOT, {"REPRO_KERNEL_MODE": "ref"})
    assert_refused(out)
    assert "REPRO_KERNEL_MODE" in out.stderr


def test_benchmark_alone_is_refused(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = run_cli(tmp_path, {})
    assert_refused(out)
