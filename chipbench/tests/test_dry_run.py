"""run.py --dry-run-cpu end to end for every cell, traced and not."""
import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT, load_bench

BENCH = load_bench(with_shelved=True)
CELLS = {w["name"]: w for w in BENCH["workloads"]}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A checkout whose BENCHMARK.json also lists the shelved cells: the
    repo's directories by symlink, so run.py takes the merged file for its
    own."""
    root = tmp_path_factory.mktemp("checkout")
    for name in ("chipbench", "incubator_mxnet_tpu"):
        os.symlink(os.path.join(ROOT, name), root / name)
    (root / "BENCHMARK.json").write_text(json.dumps(BENCH))
    return str(root)


def run(args, root=ROOT, chips=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")
    return subprocess.run([sys.executable, os.path.join(root, "chipbench", "run.py")] + args,
                          capture_output=True, text=True, cwd=root, env=env, timeout=600)


@pytest.mark.parametrize("cell", list(CELLS))
@pytest.mark.parametrize("trace", [0, 1])
def test_dry_run_prints_the_contracts_line(checkout, cell, trace):
    done = run(["--workload", cell, "--seed", "3000000011", "--seconds", "2",
                "--trace", str(trace), "--dry-run-cpu"], checkout, CELLS[cell]["chips"])
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
    assert all(ln.startswith("[chipbench") for ln in lines[:-1])
    out = json.loads(lines[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(out)
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert out["device"]["count"] == CELLS[cell]["chips"]
    assert all(m["value"] is None for m in out["metrics"].values())  # a CPU run names no figure
    section = "per_layer" if trace else "end_to_end"
    allowed = {m["name"] for m in BENCH[section]
               if "workloads" not in m or cell in m["workloads"]}
    assert set(out["metrics"]) <= allowed
    if not trace:
        assert set(out["metrics"]) == allowed


def test_no_tpu_no_result():
    done = run(["--workload", BENCH["workloads"][0]["name"], "--seed", "1",
                "--seconds", "1", "--trace", "0"])
    assert done.returncode != 0 and "no TPU" in done.stderr
    assert not done.stdout.strip().startswith("{")
