import os
import shutil
import subprocess
import sys

import harness as H

RUN = os.path.join(H.BENCH, "run.py")
ARGS = ["--workload", "shard-saturated", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, RUN, *ARGS], capture_output=True,
                       text=True, env=env, timeout=300)
    assert p.returncode == 2 and p.stdout == ""
    assert "no TPU" in p.stderr


def test_needs_the_program(tmp_path):
    shutil.copy(os.path.join(H.CHECKOUT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(H.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".*", "__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / ".jax_cache"))
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, str(tmp_path / "bench" / "run.py"),
                        *ARGS], capture_output=True, text=True, env=env,
                       cwd=tmp_path, timeout=300)
    assert p.returncode != 0 and p.stdout == ""


def test_cell_metrics_follow_the_spec():
    spec = H.spec()
    e2e = {m["name"] for m in H.cell_metrics(spec, "shard-saturated", False)}
    assert e2e == {"setup_s", "serve_qps"}
    per = {m["name"] for m in H.cell_metrics(spec, "shard-saturated", True)}
    assert "freq_topc_roofline.saturated" in per
    assert all(os.path.exists(os.path.join(H.BENCH, "metrics", f"{m}.py"))
               for m in per)
