"""The four-chip cell ``qwen72b.hw_plane``, as ``BENCHMARK.json`` has
it, on four virtual CPU devices: the grid program sharded by ``pmap``
meets the reference, and a shard that never comes back from its device
(the exchange between chips left out) makes ``correct`` false."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

SCRIPT = r"""
import json, sys
sys.path[:0] = [{bench!r}, {tests!r}]
import conftest
run = conftest.load_run()
import jax
assert jax.device_count() == 4
run.require_accelerator = lambda chips: jax.devices()
if {fault!r} == "lost_shard":
    from repro.sweep import device
    real = device._pmap_program
    def lossy(n_dev):
        prog = real(n_dev)
        def call(*args):
            out = [o.copy() for o in map(__import__("numpy").asarray,
                                         prog(*args))]
            for o in out:
                o[1] = 0          # shard 1 never arrives
            return tuple(out)
        return call
    device._pmap_program = lossy
sys.exit(run.main(["--workload", "qwen72b.hw_plane", "--seed",
                   "18446744073709551629", "--seconds", "0.01",
                   "--trace", "0"], root=conftest.Path({root!r})))
"""


def four_chip_root(tmp_path):
    """A checkout root with the benchmark's manifest, deployments and
    traffic mixes."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for d in ("configs", "traffic"):
        shutil.copytree(BENCH / d, tmp_path / "bench" / d)
    return tmp_path


@pytest.mark.parametrize("fault", ["none", "lost_shard"])
def test_sharded_dispatch(fault, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    src = SCRIPT.format(bench=str(BENCH), tests=str(BENCH / "tests"),
                        fault=fault, root=str(four_chip_root(tmp_path)))
    p = subprocess.run([sys.executable, "-c", src], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["device"]["count"] == 4
    assert "on 4x cpu" in p.stderr
    assert res["correct"] is (fault == "none"), res["check"]
