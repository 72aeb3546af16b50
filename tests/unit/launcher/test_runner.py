"""Launcher CLI: hostfile parsing, include/exclude filters, world-info
encoding, per-node env layout, end-to-end local launch.

Mirrors the reference's ``tests/unit/launcher/test_ds_arguments.py`` /
``test_run.py`` coverage (SURVEY.md §4).
"""

import base64
import json
import os
import subprocess
import sys
from collections import OrderedDict
from pathlib import Path

REPO_ROOT = str(Path(__file__).resolve().parents[3])

import pytest

from deepspeed_tpu.launcher.runner import (encode_world_info, fetch_hostfile,
                                           filter_resource_pool)


def _write_hostfile(tmp_path, text):
    p = tmp_path / "hostfile"
    p.write_text(text)
    return str(p)


def test_fetch_hostfile(tmp_path):
    path = _write_hostfile(tmp_path, """
# comment
worker-0 slots=4
worker-1 slots=2
""")
    pool = fetch_hostfile(path)
    assert pool == OrderedDict([("worker-0", 4), ("worker-1", 2)])


def test_fetch_hostfile_missing_returns_none():
    assert fetch_hostfile("/nonexistent/hostfile") is None


def test_fetch_hostfile_malformed_raises(tmp_path):
    path = _write_hostfile(tmp_path, "worker-0 gpus=4\n")
    with pytest.raises(ValueError):
        fetch_hostfile(path)


def test_include_filter():
    pool = OrderedDict([("a", 4), ("b", 4), ("c", 4)])
    out = filter_resource_pool(pool, include="a@c:0,1", exclude="")
    assert out == OrderedDict([("a", 4), ("c", 2)])


def test_exclude_filter():
    pool = OrderedDict([("a", 4), ("b", 4)])
    out = filter_resource_pool(pool, include="", exclude="b")
    assert out == OrderedDict([("a", 4)])
    out = filter_resource_pool(pool, include="", exclude="a:0,1")
    assert out == OrderedDict([("a", 2), ("b", 4)])


def test_include_and_exclude_mutually_exclusive():
    with pytest.raises(ValueError):
        filter_resource_pool(OrderedDict(a=1), include="a", exclude="a")


def test_world_info_roundtrip():
    pool = OrderedDict([("h1", 1), ("h2", 1)])
    blob = encode_world_info(pool)
    decoded = json.loads(base64.urlsafe_b64decode(blob.encode()))
    assert decoded == {"h1": 1, "h2": 1}


def test_local_launch_end_to_end(tmp_path):
    """launch.py spawns ranks with the full rendezvous env set."""
    script = tmp_path / "probe.py"
    # ranks write to per-rank files: concurrent stdout lines can interleave
    script.write_text(
        "import os, json\n"
        "d = {k: os.environ[k] for k in "
        "('RANK','LOCAL_RANK','WORLD_SIZE','DS_COORDINATOR',"
        "'DS_PROCESS_ID','DS_NUM_PROCESSES')}\n"
        f"open(r'{tmp_path}/rank' + os.environ['RANK'] + '.json', 'w')"
        ".write(json.dumps(d))\n")
    world = encode_world_info(OrderedDict([("localhost", 2)]))
    out = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.launcher.launch",
         f"--world_info={world}", "--node_rank=0",
         "--master_addr=127.0.0.1", "--master_port=29777", str(script)],
        capture_output=True, text=True, timeout=120,
        cwd=REPO_ROOT, env={**os.environ, "PYTHONPATH": REPO_ROOT})
    assert out.returncode == 0, out.stderr
    envs = [json.loads((tmp_path / f"rank{r}.json").read_text())
            for r in (0, 1)]
    assert len(envs) == 2
    ranks = sorted(int(e["RANK"]) for e in envs)
    assert ranks == [0, 1]
    for e in envs:
        assert e["WORLD_SIZE"] == "2"
        assert e["DS_COORDINATOR"] == "127.0.0.1:29777"
        assert e["DS_NUM_PROCESSES"] == "2"


def test_ds_report_runs():
    out = subprocess.run(
        [sys.executable, "-c",
         "from deepspeed_tpu.env_report import cli_main; cli_main()"],
        capture_output=True, text=True, timeout=300,
        cwd=REPO_ROOT, env={**os.environ, "PYTHONPATH": REPO_ROOT,
                             "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr
    assert "C++ op report" in out.stdout
    assert "cpu_adam" in out.stdout


# compile-heavy: full-suite / slow tier only (fast tier = pytest -m "not slow")
import pytest as _pytest_tier
pytestmark = _pytest_tier.mark.slow
