"""Shared by the chip benchmark's tests: where things are, and the tiny
sizes every cell is rehearsed at on the CPU.  The sizes are data under
``tiny/``, found by the configuration's, the kind's and the traffic file's
name (``tiny/README.txt``), so a cell added as files rehearses as it is."""

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
BENCH_DIR = os.path.join(ROOT, "benchmarks", "chip")


def _read(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def benchmark() -> dict:
    return _read(ROOT, "BENCHMARK.json")


def tiny_config(config: str) -> dict:
    """The keys of one configuration file cut to 2 layers of d64."""
    path = os.path.join(HERE, "tiny", "configs", config + ".json")
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"configuration {config!r} has no tiny sizes: add {path} "
            f"(see tiny/README.txt)")
    return _read(path)


def tiny_overrides(cell: dict) -> dict:
    """The rehearsal's overrides for one ``workloads`` entry."""
    kind = _read(BENCH_DIR, "traffic", cell["traffic"] + ".json")["kind"]
    out = _read(HERE, "tiny", "kinds", kind + ".json")
    own = os.path.join(HERE, "tiny", "traffic", cell["traffic"] + ".json")
    if os.path.exists(own):
        for key, value in _read(own).items():
            out[key] = {**out[key], **value} if isinstance(
                out.get(key), dict) else value
    return {"config_file": tiny_config(cell["config"]), **out}
