"""chip_smoke.py's off-chip contract (each case one subprocess, the
way the driver runs it): the default invocation refuses anything but
a TPU and prints no result; `--cpu-dryrun` drives the whole two-tier
flow, kernel leg and 4-device mesh leg at tiny sizes and is green; the
compile cache goes where JAX_COMPILATION_CACHE_DIR says and nowhere
else."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def run_smoke(args, cwd, **env):
    full = {k: v for k, v in os.environ.items()
            if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    full.update(JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, SMOKE, *args], cwd=cwd,
                          env=full, capture_output=True, text=True,
                          timeout=600)


def test_default_invocation_refuses_the_cpu(tmp_path):
    p = run_smoke([], str(tmp_path))
    assert p.returncode != 0
    assert "'cpu'" in p.stderr and "needs a TPU" in p.stderr
    # no result: nothing on stdout a driver could take for one
    assert p.stdout.strip() == ""


def test_cpu_dryrun_is_green_and_caches_where_told(tmp_path):
    cache = tmp_path / "cache"
    checkout_cache = os.path.join(REPO, ".jax_cache")
    before = (sorted(os.listdir(checkout_cache))
              if os.path.isdir(checkout_cache) else None)
    p = run_smoke(["--cpu-dryrun"], str(tmp_path),
                  JAX_COMPILATION_CACHE_DIR=str(cache))
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["ok"] is True and last["dryrun"] is True
    assert last["device"] == {"platform": "cpu", "kind": "cpu",
                              "count": 4}
    assert "legs_ran: ['kernels', 'tiers', 'mesh']" in p.stdout
    # the cache is placed from outside: entries there, none in the
    # checkout
    assert any(cache.iterdir())
    after = (sorted(os.listdir(checkout_cache))
             if os.path.isdir(checkout_cache) else None)
    assert after == before
    # and the report the chip tool carries home was written
    report = json.loads(
        (tmp_path / "chiprun_out" / "chip_smoke_report.json").read_text())
    assert report["failures"] == [] and report["dryrun"] is True
    assert report["legs"]["tiers"]["windows"][2]["compile"][
        "programs"] == 0
