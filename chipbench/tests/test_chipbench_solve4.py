"""The four-chip solve cell's check, driven on the CPU on 4 virtual
devices at 8 rows a chip: a sound run passes it; the ``high`` control
and each planted fault, the exchange between the chips left out among
them, fail it.

Four virtual devices need ``XLA_FLAGS`` set before JAX starts, so each
case runs this file as a script in a process of its own:
``python test_chipbench_solve4.py <case>`` prints the run's verdict."""

import benchpaths  # noqa: F401  (first: puts the harness on the path)

import json
import os
import subprocess
import sys

import pytest

CELL = "solve.mlp256.heavy.4chip"
FAULTS = ["no_exchange", "unchanged", "half_batch", "altered"]


def _case(case):
    import jax

    from cellrun import run
    from harness import faults

    if jax.device_count() < 4:
        raise SystemExit(f"needs 4 devices, JAX sees {jax.device_count()}")
    if case in FAULTS:
        faults.plant("solve", case)
    r = run(CELL, control=case == "control")
    return {k: r[k] for k in ("correct", "attempted", "failed", "checks")}


@pytest.mark.parametrize("case", ["sound", "control"] + FAULTS)
def test_four_chip_check(case):
    flags = os.environ.get("XLA_FLAGS", "")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"{flags} --xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, os.path.abspath(__file__), case],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    if case == "sound":
        assert r["correct"], r["checks"]
        assert r["failed"] == 0 and r["attempted"] > 0
    else:
        assert not r["correct"], (case, r["checks"])


if __name__ == "__main__":
    print(json.dumps(_case(sys.argv[1])), flush=True)
