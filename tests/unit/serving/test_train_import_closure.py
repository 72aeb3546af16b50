"""The benchmark's training kind loads nothing of ``deepspeed_tpu.serving``.

A train cell's process imports what ``benchmarks/chip/run.py`` imports for
every kind (the harness, the model builder, the platform helpers) and then
what ``benchmarks/chip/kinds/train.py`` imports, at its top and inside
``run``.  This test reads those import statements from the source, executes
exactly them in a new interpreter and looks at ``sys.modules``: with no
serving module loaded, a PR whose diff stays inside ``deepspeed_tpu/serving/``
cannot have moved anything a train cell measures, ``setup_s`` included.
"""

import ast
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
PACKAGE = "benchmarks.chip.kinds"


def _imports_of(path, function=None):
    """The import statements of a module's top level, or of one of its
    functions, as source lines that run anywhere (relative ones made
    absolute)."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read())
    body = tree.body
    if function is not None:
        body = [n for n in ast.walk(tree) if isinstance(n, ast.FunctionDef)
                and n.name == function][0].body
    lines = []
    for node in body:
        if isinstance(node, ast.ImportFrom) and node.level:
            base = PACKAGE.rsplit(".", node.level - 1)[0] \
                if node.level > 1 else PACKAGE
            node.module = f"{base}.{node.module}" if node.module else base
            node.level = 0
        if isinstance(node, (ast.Import, ast.ImportFrom)) and not (
                isinstance(node, ast.ImportFrom)
                and node.module == "__future__"):
            lines.append(ast.unparse(node))
    return lines


def test_the_train_kind_imports_no_serving_module():
    kind = os.path.join(ROOT, "benchmarks", "chip", "kinds", "train.py")
    lines = (["import benchmarks.chip.harness",
              "import benchmarks.chip.builders",
              "import benchmarks.chip.peaks",
              "from deepspeed_tpu.utils.platform import enable_compile_cache"]
             + _imports_of(kind) + _imports_of(kind, "run"))
    assert "import deepspeed_tpu" in lines
    assert any("deepspeed_tpu.runtime.model" in l for l in lines)
    script = "\n".join(lines + [
        "import json, sys",
        "mods = sorted(m for m in sys.modules"
        " if m.split('.')[0] == 'deepspeed_tpu')",
        "print(json.dumps(mods))"])
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    out = subprocess.run([sys.executable, "-c", script], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    mods = json.loads(out.stdout.strip().splitlines()[-1])
    assert "deepspeed_tpu.runtime.engine" in mods and len(mods) > 40
    serving = [m for m in mods if m.startswith("deepspeed_tpu.serving")]
    assert serving == [], serving
