import pytest

pytestmark = pytest.mark.slow
"""The driver's round gates, as tests (round 1 failed on exactly these
being unexercised): __graft_entry__ must expose a compilable entry() and a
dryrun that executes real shardings.  (That chip_smoke.py refuses to run
without a chip is a tier-1 test: tests/unit/test_chip_entry.py.)

Both run in subprocesses: the gates themselves bootstrap jax platforms,
which must happen in a fresh interpreter (the latched-backend hazard the
platform helper documents).
"""

import os
import subprocess
import sys

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


def _run(code, timeout=540, env_extra=None):
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO})
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=timeout, cwd=REPO, env=env)


def test_entry_is_jittable():
    r = _run(
        "from deepspeed_tpu.utils.platform import force_cpu_platform\n"
        "force_cpu_platform(1)\n"
        "import jax\n"
        "import __graft_entry__ as g\n"
        "fn, args = g.entry()\n"
        "out = jax.jit(fn)(*args)\n"
        "print('ENTRY_OK', out.shape)\n")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "ENTRY_OK" in r.stdout


def test_dryrun_multichip_all_phases():
    r = _run("import __graft_entry__ as g; g.dryrun_multichip(8)")
    assert r.returncode == 0, r.stderr[-2000:]
    for phase in ("dryrun_multichip(8) OK", "moe(ep=2", "sp(ring",
                  "pipeline(pp=4"):
        assert phase in r.stdout, (phase, r.stdout[-2000:])
