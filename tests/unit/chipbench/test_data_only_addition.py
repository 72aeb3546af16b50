"""A later PR adds a cell by adding files and entries and editing nothing
that is there.  In a copy of the benchmark and of these tests: one
configuration file, one traffic file, one metric file with a reader of its
own, the tiny sizes of the two, and the entries of ``BENCHMARK.json``.  Then
the copy's own tests, as committed, validate the entries and rehearse the
new cell: the suite takes a cell added as data as it is."""

import json
import os
import re
import shutil
import subprocess
import sys

from .common import ROOT

READER = '''"""The largest late send of the run, in ms."""


def read(ctx):
    values = ctx.samples.get("late_ms")
    return max(values) if values else None
'''
CELL = "gpt2-small-chat-bursty"


def _digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            if "__pycache__" not in d:
                with open(os.path.join(d, f), "rb") as fh:
                    out[os.path.relpath(os.path.join(d, f), root)] = fh.read()
    return out


def _write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(obj if isinstance(obj, str) else json.dumps(obj))


def test_a_cell_is_added_as_data(tmp_path):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for path in bench["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for rel in ("tests/__init__.py", "tests/unit/__init__.py",
                "tests/conftest.py", "pytest.ini"):
        shutil.copy(os.path.join(ROOT, rel), tmp_path / rel)
    before = _digest(tmp_path)
    chip = tmp_path / "benchmarks" / "chip"
    tiny = tmp_path / "tests" / "unit" / "chipbench" / "tiny"

    # 1. a configuration: GPT-2 small, its own file, the existing builder
    with open(chip / "configs" / "gpt2-medium.json") as f:
        config = json.load(f)
    config.update(name="gpt2-small", n_layer=12, n_embd=768, n_head=12,
                  source="https://huggingface.co/openai-community/gpt2")
    _write(chip / "configs" / "gpt2-small.json", config)
    shutil.copy(tiny / "configs" / "gpt2-medium.json",
                tiny / "configs" / "gpt2-small.json")
    # 2. a traffic mix: the steady chat's parameters plus bursts
    with open(chip / "traffic" / "chat-steady.json") as f:
        traffic = json.load(f)
    traffic["burst"] = {"every_s": 4.0, "len_s": 1.5, "factor": 3.0}
    _write(chip / "traffic" / "chat-bursty.json", traffic)
    _write(tiny / "traffic" / "chat-bursty.json", {"traffic": {
        "burst": {"every_s": 0.5, "len_s": 0.2, "factor": 3.0}}})
    # 3. a per-layer metric with a reader of its own
    _write(chip / "metrics" / "loadgen.late_max_ms.json",
           {"reader": "late_max", "args": {}, "what": "the latest send"})
    _write(chip / "metrics" / "readers" / "late_max.py", READER)
    # 4. the entries
    bench["configs"].append({
        "name": "gpt2-small", "source": config["source"], "reduced": [],
        "file": "benchmarks/chip/configs/gpt2-small.json", "why": "test"})
    bench["workloads"].append({
        "name": CELL, "config": "gpt2-small", "traffic": "chat-bursty",
        "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "loadgen.late_max_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "load generator",
        "moves": "ttft_p95_ms", "workloads": [CELL]})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "gpt2m-serve-chat-steady" in m.get("workloads", []) \
                and m["name"].startswith(("ttft", "tpot", "loadgen.late_p95")):
            m["workloads"].append(CELL)
    _write(tmp_path / "BENCHMARK.json", bench)

    after = _digest(tmp_path)
    assert all(after[k] == v for k, v in before.items())   # nothing edited
    assert len(after) == len(before) + 7

    # the copy's tests as committed: BENCHMARK.json's contract with the new
    # entries, and the parametrised rehearsal of the new cell (both runs)
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:randomly", "-k", f"{CELL} or test_benchmark_json",
         "tests/unit/chipbench/test_benchmark_json.py",
         "tests/unit/chipbench/test_rehearsal.py"],
        capture_output=True, text=True, timeout=900, cwd=str(tmp_path),
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": f"{tmp_path}{os.pathsep}{ROOT}"})
    tail = proc.stdout[-3000:] + proc.stderr[-2000:]
    assert proc.returncode == 0, tail
    passed = int(re.search(r"(\d+) passed", proc.stdout).group(1))
    n_metrics = len(bench["end_to_end"]) + len(bench["per_layer"])
    # both rehearsals of the new cell, and an entry test and a reader test
    # for each metric, the new one among them
    assert passed >= 2 + 2 * n_metrics, tail
    assert "failed" not in proc.stdout.splitlines()[-1], tail
