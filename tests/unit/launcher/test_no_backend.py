"""The launcher's parents only spawn: a process that has initialised a JAX
backend holds the chip, and the children it starts then cannot have it.
Importing the package imports jax, which is harmless until a device is asked
for; after building and running their child commands neither ``runner`` nor
``launch`` may have a backend."""

import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                         "..", "..", ".."))

_PARENT = r"""
import sys
from jax._src import xla_bridge
from deepspeed_tpu.launcher import {module}
rc = {module}.main({argv!r})
assert rc == 0, rc
assert not xla_bridge.backends_are_initialized(), \
    "the launcher parent initialised a JAX backend"
print("NO_BACKEND_IN_PARENT")
"""


@pytest.mark.parametrize("module,argv", [
    ("runner", ["--num_gpus", "2", "--master_port", "29781", "{script}"]),
    ("launch", ["--world_info", "eyJsb2NhbGhvc3QiOiAyfQ==", "--node_rank=0",
                "--master_addr=127.0.0.1", "--master_port=29782",
                "{script}"]),
])
def test_parent_never_initialises_a_backend(tmp_path, module, argv):
    script = tmp_path / "child.py"
    script.write_text("import os\nassert os.environ['WORLD_SIZE'] == '2'\n")
    argv = [a.format(script=script) for a in argv]
    out = subprocess.run(
        [sys.executable, "-c", _PARENT.format(module=module, argv=argv)],
        capture_output=True, text=True, timeout=120, cwd=REPO_ROOT,
        env={**os.environ, "PYTHONPATH": REPO_ROOT})
    assert out.returncode == 0, out.stderr[-2000:]
    assert "NO_BACKEND_IN_PARENT" in out.stdout
