"""A later PR adds a cell by adding files and entries and editing nothing
that is there.  In a copy of the benchmark and of these tests: one
configuration file, one traffic file, one metric file with a reader of its
own, the tiny sizes of the two, and the entries of ``BENCHMARK.json``.  Then
the copy's own tests, as committed, validate the entries and rehearse the
new cell: the suite takes a cell added as data as it is.

The second case adds a configuration of *another family* the program
serves (the GPT-MoE family of ``models/gpt_moe.py``: every other layer's
FFN is a top-1 expert layer) the same way: its configuration file, a module
with its builder and its init, a plain reference of its own, a traffic file
on ``backlog``, its tiny sizes and its entries.  No kind, reader or test is
edited: that is what a ``model_config`` PR relies on.

The third case adds a configuration of a family the program does *not*
serve yet, whose cache row is not ``2 H D`` and whose config has no
``head_dim`` (latent attention: the architecture issue 32 drew), with a
counting function and a metric file of its own.  The copy's tests of
``BENCHMARK.json``, of the configuration file and of its hooks pass as
committed; its rehearsal waits for the program code that serves it."""

import json
import os
import re
import shutil
import subprocess
import sys

from .common import LATENT, LATENT_SOURCE, ROOT

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


def _copy_of_the_benchmark(tmp_path):
    """``(bench, digest, benchmarks/chip, tiny)`` of a copy of everything
    under ``paths`` with what its tests need around it."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for path in bench["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for rel in ("tests/__init__.py", "tests/unit/__init__.py",
                "tests/conftest.py", "pytest.ini"):
        shutil.copy(os.path.join(ROOT, rel), tmp_path / rel)
    return (bench, _digest(tmp_path), tmp_path / "benchmarks" / "chip",
            tmp_path / "tests" / "unit" / "chipbench" / "tiny")


def _the_copys_tests(tmp_path, cell, *files):
    """The copy's tests as committed: ``BENCHMARK.json``'s contract with the
    new entries, and the parametrised tests that name the new cell."""
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:randomly", "-k", f"{cell} or test_benchmark_json",
         "tests/unit/chipbench/test_benchmark_json.py",
         "tests/unit/chipbench/test_rehearsal.py", *files],
        capture_output=True, text=True, timeout=900, cwd=str(tmp_path),
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": f"{tmp_path}{os.pathsep}{ROOT}"})
    tail = proc.stdout[-3000:] + proc.stderr[-2000:]
    assert proc.returncode == 0, tail
    assert "failed" not in proc.stdout.splitlines()[-1], tail
    return int(re.search(r"(\d+) passed", proc.stdout).group(1)), tail


def test_a_cell_is_added_as_data(tmp_path):
    bench, before, chip, tiny = _copy_of_the_benchmark(tmp_path)

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

    passed, tail = _the_copys_tests(tmp_path, CELL)
    n_metrics = len(bench["end_to_end"]) + len(bench["per_layer"])
    # both rehearsals of the new cell, and an entry test and a reader test
    # for each metric, the new one among them
    assert passed >= 2 + 2 * n_metrics, tail


# ---- a configuration of another family, as files ------------------------

MOE_CELL = "gptmoe-serve-decode-sat"
MOE_FAMILY = '''"""The GPT-MoE family (``models/gpt_moe.py``): GPT-2's block, every other
layer's FFN a top-1 expert layer of ``num_experts`` experts of width
``4 d``."""


def build(file):
    from deepspeed_tpu.models.gpt_moe import GPTMoEConfig
    return GPTMoEConfig(
        vocab_size=file["vocab_size"], max_seq_len=file["n_positions"],
        n_layer=file["n_layer"], n_head=file["n_head"],
        d_model=file["n_embd"], d_ff=file["n_inner"], activation="gelu",
        pos_embed="learned", tie_word_embeddings=True,
        num_experts=file["num_experts"],
        moe_top_k=file["num_experts_per_tok"])


build.published = lambda c: {
    "n_embd": c.d_model, "n_inner": c.ffn_dim, "n_head": c.n_head,
    "n_layer": c.n_layer, "n_positions": c.max_seq_len,
    "vocab_size": c.vocab_size, "num_experts": c.num_experts,
    "num_experts_per_tok": c.moe_top_k}


def init(cfg, key, dtype=None):
    import jax
    from deepspeed_tpu.models import gpt_moe
    params = gpt_moe.init(cfg, key)
    if dtype is None:
        return params
    return jax.tree_util.tree_map(lambda x: x.astype(dtype), params)
'''
MOE_REFERENCE = '''"""The plain reference of the GPT-MoE family in float32 ``jax.numpy``:
layers come in pairs, a GPT-2 block and then a block whose FFN is the expert
the gate scores highest, weighted by its softmax probability (top-1, no
token dropped).  No kernel, no cache, no scan, nothing of the program."""

import jax
import jax.numpy as jnp


def _ln(x, p, which, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p[which + "_scale"] \\
        + p[which + "_bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def _attend(x, p, causal):
    qkv = jnp.einsum("bsd,dthe->bsthe", _ln(x, p, "ln1"), p["wqkv"]) \\
        + p["bqkv"]
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    s = jnp.einsum("bqhe,bkhe->bhqk", q, k) / jnp.sqrt(
        jnp.float32(q.shape[-1]))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhe->bqhe", jax.nn.softmax(s, -1), v)
    return x + jnp.einsum("bqhe,hed->bqd", a, p["wo"]) + p["bo"]


def forward(file, params, tokens, last):
    f32 = lambda t: jax.tree_util.tree_map(
        lambda a: jnp.asarray(a).astype(jnp.float32), t)
    B, S = tokens.shape
    with jax.default_matmul_precision("highest"):
        x = f32(params["wte"])[tokens] + f32(params["wpe"])[
            jnp.arange(S)][None]
        causal = jnp.tril(jnp.ones((S, S), bool))
        for i in range(file["n_layer"] // 2):
            at = lambda t: f32(jax.tree_util.tree_map(lambda a: a[i], t))
            p = at(params["dense_blocks"])
            x = _attend(x, p, causal)
            h = _ln(x, p, "ln2")
            x = x + _gelu(h @ p["wi"] + p["bi"]) @ p["wo_mlp"] + p["bo_mlp"]
            p, moe = at(params["moe_attn_blocks"]), at(params["moe_blocks"])
            x = _attend(x, p, causal)
            h = _ln(x, p, "ln2")
            gates = jax.nn.softmax(h @ moe["gate"]["wg"], -1)
            e = moe["experts"]
            every = jnp.einsum(
                "bsef,efd->bsed",
                _gelu(jnp.einsum("bsd,edf->bsef", h, e["wi"]) + e["bi"]),
                e["wo"]) + e["bo"]
            best = jnp.argmax(gates, -1)
            pick = lambda a: jnp.take_along_axis(
                a, best[..., None, None], axis=2)[:, :, 0]
            x = x + jnp.max(gates, -1, keepdims=True) * pick(every)
        x = _ln(x[:, S - last:], f32({
            "lnf_scale": params["lnf_scale"],
            "lnf_bias": params["lnf_bias"]}), "lnf")
        return (x @ f32(params["wte"]).T)[..., :file["vocab_size"]]
'''


def test_a_configuration_of_another_family_is_added_as_files(tmp_path):
    bench, before, chip, tiny = _copy_of_the_benchmark(tmp_path)
    source = "https://arxiv.org/abs/2201.05596"
    # 1. the configuration file with its hooks, and its tiny sizes
    _write(chip / "configs" / "gpt-moe-350m-64e.json", {
        "name": "gpt-moe-350m-64e", "source": source,
        "builder": "gpt_moe_family.build", "init": "gpt_moe_family.init",
        "reference": "reference.gpt_moe_reference",
        "n_layer": 24, "n_embd": 1024, "n_head": 16, "n_inner": 4096,
        "n_positions": 1024, "vocab_size": 50257, "num_experts": 64,
        "num_experts_per_tok": 1, "reduced": []})
    _write(tiny / "configs" / "gpt-moe-350m-64e.json", {
        "vocab_size": 512, "n_layer": 2, "n_head": 4, "n_embd": 64,
        "n_inner": 256, "n_positions": 128, "num_experts": 4})
    # 2. the family's builder and init, and its plain reference
    _write(chip / "gpt_moe_family.py", MOE_FAMILY)
    _write(chip / "reference" / "gpt_moe_reference.py", MOE_REFERENCE)
    # 3. a traffic file on the backlog kind
    with open(chip / "traffic" / "decode-sat.json") as f:
        traffic = json.load(f)
    _write(chip / "traffic" / "moe-decode-sat.json", traffic)
    # 4. the entries
    bench["configs"].append({
        "name": "gpt-moe-350m-64e", "source": source, "reduced": [],
        "file": "benchmarks/chip/configs/gpt-moe-350m-64e.json",
        "why": "test"})
    bench["workloads"].append({
        "name": MOE_CELL, "config": "gpt-moe-350m-64e",
        "traffic": "moe-decode-sat", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "gpt2m-serve-decode-sat" in m.get("workloads", []):
            m["workloads"].append(MOE_CELL)
    _write(tmp_path / "BENCHMARK.json", bench)

    after = _digest(tmp_path)
    assert all(after[k] == v for k, v in before.items())   # nothing edited
    assert len(after) == len(before) + 6

    passed, tail = _the_copys_tests(
        tmp_path, MOE_CELL + " or gpt-moe-350m-64e",
        "tests/unit/chipbench/test_any_family.py")
    n_metrics = len(bench["end_to_end"]) + len(bench["per_layer"])
    # both rehearsals of the new cell, the configuration's file and its
    # hooks, and an entry test and a reader test for each metric
    assert passed >= 2 + 2 + 2 * n_metrics, tail


# ---- a family with another cache row, as files --------------------------

LATENT_CELL = "lat-serve-decode-sat"
LATENT_FAMILY = '''"""A latent-attention, routed-expert family: a cached token is one
compressed row of ``kv_lora_rank + qk_rope_head_dim`` elements a layer,
shared by every head; there is no ``head_dim`` and ``hidden_size`` is not a
number of heads times anything."""

import dataclasses


@dataclasses.dataclass(frozen=True)
class Config:
    d_model: int
    n_head: int
    d_ff: int
    d_expert: int
    q_rank: int
    kv_rank: int
    d_nope: int
    d_rope: int
    d_v: int
    experts_per_token: int
    n_layer: int
    n_experts: int
    vocab_size: int


def build(file):
    return Config(
        file["hidden_size"], file["num_attention_heads"],
        file["intermediate_size"], file["moe_intermediate_size"],
        file["q_lora_rank"], file["kv_lora_rank"], file["qk_nope_head_dim"],
        file["qk_rope_head_dim"], file["v_head_dim"],
        file["num_experts_per_tok"], file["num_hidden_layers"],
        file["n_routed_experts"], file["vocab_size"])


build.published = lambda c: {
    "hidden_size": c.d_model, "num_attention_heads": c.n_head,
    "intermediate_size": c.d_ff, "moe_intermediate_size": c.d_expert,
    "q_lora_rank": c.q_rank, "kv_lora_rank": c.kv_rank,
    "qk_nope_head_dim": c.d_nope, "qk_rope_head_dim": c.d_rope,
    "v_head_dim": c.d_v, "num_experts_per_tok": c.experts_per_token,
    "num_hidden_layers": c.n_layer, "n_routed_experts": c.n_experts,
    "vocab_size": c.vocab_size}


def init(cfg, key, dtype=None):
    raise NotImplementedError("comes with the program code that serves it")


def decode_count(cfg, rows_context_tokens):
    """``(operations, bytes)`` one decode call of one layer must spend on
    ``rows_context_tokens`` cached tokens: every head scores the one latent
    row (``kv_rank + d_rope``) and weighs its ``kv_rank`` values (the
    absorbed form); the row is read once, two bytes an element."""
    row = cfg.kv_rank + cfg.d_rope
    return (2.0 * rows_context_tokens * cfg.n_head * (row + cfg.kv_rank),
            2.0 * rows_context_tokens * row)
'''
LATENT_REFERENCE = '''"""The plain reference of the latent-attention family: comes with the
program code that serves it."""


def forward(file, params, tokens, last):
    raise NotImplementedError
'''


def test_a_family_with_another_cache_row_brings_a_count_as_files(tmp_path):
    bench, before, chip, tiny = _copy_of_the_benchmark(tmp_path)
    reduced = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    # 1. the configuration: the issue's one-chip cut, with its hooks
    _write(chip / "configs" / "latent-cut.json", {
        **LATENT, "name": "latent-cut", "source": LATENT_SOURCE,
        "builder": "latent_family.build", "init": "latent_family.init",
        "reference": "reference.latent_reference",
        "num_hidden_layers": 5, "n_routed_experts": 12, "vocab_size": 20480,
        "reduced": reduced, "deployment_chips_per_layer": 32,
        "published": {"num_hidden_layers": 61, "n_routed_experts": 384,
                      "vocab_size": 163840}})
    # 2. the family's module (builder, init, the count) and its reference
    _write(chip / "latent_family.py", LATENT_FAMILY)
    _write(chip / "reference" / "latent_reference.py", LATENT_REFERENCE)
    # 3. a traffic file, and a metric file that names the family's count
    shutil.copy(chip / "traffic" / "decode-sat.json",
                chip / "traffic" / "latent-decode-sat.json")
    _write(chip / "metrics" / "kernels.decode_attention_roofline.latent.json",
           {"reader": "decode_roofline",
            "args": {"count": "latent_family.decode_count"},
            "what": "decode custom call against the latent rows it reads"})
    # 4. the entries
    bench["configs"].append({
        "name": "latent-cut", "source": LATENT_SOURCE, "reduced": reduced,
        "file": "benchmarks/chip/configs/latent-cut.json", "why": "test"})
    bench["workloads"].append({
        "name": LATENT_CELL, "config": "latent-cut",
        "traffic": "latent-decode-sat", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if "gpt2m-serve-decode-sat" in m.get("workloads", []):
            m["workloads"].append(LATENT_CELL)
    bench["per_layer"].append({
        "name": "kernels.decode_attention_roofline.latent", "unit": "%",
        "better": "higher", "source": "device_trace", "layer": "kernels",
        "moves": "serve_tokens_per_s", "workloads": [LATENT_CELL]})
    _write(tmp_path / "BENCHMARK.json", bench)

    after = _digest(tmp_path)
    assert all(after[k] == v for k, v in before.items())   # nothing edited
    assert len(after) == len(before) + 6

    # the configuration's file and its hooks, by the configuration's name:
    # the cell's rehearsal is not among them (nothing serves the family yet)
    passed, tail = _the_copys_tests(
        tmp_path, "latent-cut or test_benchmark_json",
        "tests/unit/chipbench/test_any_family.py")
    n_metrics = len(bench["end_to_end"]) + len(bench["per_layer"])
    assert passed >= 2 + 2 * n_metrics, tail
    # by name: the two tests that refused another family before PR 32, and
    # the count the hooks test took is the one the metric file names
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": f"{tmp_path}{os.pathsep}{ROOT}"}
    named = subprocess.run(
        [sys.executable, "-m", "pytest", "-v", "-p", "no:cacheprovider",
         "-k", "latent-cut", "tests/unit/chipbench/test_any_family.py",
         "tests/unit/chipbench/test_benchmark_json.py"],
        capture_output=True, text=True, cwd=str(tmp_path), env=env)
    for name in ("test_hooks_resolve[latent-cut] PASSED",
                 "test_configuration_file[latent-cut] PASSED"):
        assert name in named.stdout, named.stdout[-2000:]
    probe = subprocess.run(
        [sys.executable, "-c",
         "from tests.unit.chipbench import test_any_family as t\n"
         "c = next(c for c in t.BENCH['configs'] if c['name']=='latent-cut')\n"
         "print(t._decode_counts(c)[0])"],
        capture_output=True, text=True, cwd=str(tmp_path), env=env)
    assert "['latent_family.decode_count']" in probe.stdout, probe.stderr
