"""The named scopes that split a program's device time
(``telemetry/op_maps.py``) are metadata: with each of them taken away, the
tick and the admission compile to the same optimised HLO but for metadata
(an instruction's name is made of its ``op_name``, so names are compared by
where they first appear).  CPU lowering; the described-v5e compiles of
``tests/unit/ops/test_tpu_aot_compile.py`` guard the chip's."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax._src import source_info_util

from deepspeed_tpu.models import cache_family, gpt
from deepspeed_tpu.serving.batcher import admission

#: the scopes PR 38 opened, and where
SCOPES = {
    "project": "models/gpt_inference.py::_layer_scan",
    "sweep": "models/gpt_inference.py::decode_step",
    "qkv": "models/gpt.py::qkv_proj",
    "attn_out": "models/gpt.py::attn_project",
    "norm": "models/gpt.py::_layer_norm",
    "admit_chunk": "serving/batcher.py::admission",
    "admit_head": "serving/batcher.py::admission",
    "admit_row_cache": "serving/batcher.py::admission",
    "admit_slot_write": "serving/batcher.py::admission",
    "admit_bind": "serving/batcher.py::admission",
}
#: the scopes each program's compiled text names (the CPU's tick has no
#: kernel, so no work list: ``sweep`` is dead code there; the row cache's
#: zero-fill is re-made by the compiler as a broadcast of a constant that
#: carries no name, on the CPU as on the chip: PERF.md 6, PR 38).  The dense
#: bf16 admission works on the slot's own row of the pool (PR 63): it makes
#: no row cache and writes no slot; ``admit_apart`` is the same family sent
#: through the batch-1 row cache, whose scopes those two stay
PRESENT = {"tick": {"project", "qkv", "attn_out", "norm"},
           "admit": set(SCOPES) - {"sweep", "admit_row_cache",
                                   "admit_slot_write"},
           "admit_apart": set(SCOPES) - {"sweep", "admit_row_cache"}}
CFG = dataclasses.replace(gpt.GPT2_350M, n_layer=2, d_model=64, n_head=4,
                          vocab_size=256, max_seq_len=64,
                          dtype=jnp.bfloat16)
SLOTS, SMAX, CHUNK = 3, 64, 8


def _described(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def _lowered(program):
    fam = cache_family(CFG)
    params = _described(jax.eval_shape(
        lambda: gpt.init(CFG, jax.random.PRNGKey(0))))
    pool = _described(jax.eval_shape(
        lambda: fam.init_cache(CFG, SLOTS, SMAX)))
    arg = jax.ShapeDtypeStruct
    rows, live = arg((SLOTS,), jnp.int32), arg((SLOTS,), jnp.bool_)
    if program == "tick":
        return jax.jit(
            lambda p, c, tok, lengths, active: fam.decode_step(
                p, tok, CFG, c, lengths=lengths, active=active),
            donate_argnums=(1,)).lower(params, pool, rows, rows, live)
    vocab = jax.eval_shape(lambda p, t, c: fam.extend(p, t, CFG, c)[0],
                           params, arg((1, CHUNK), jnp.int32), _described(
        jax.eval_shape(lambda: fam.init_cache(CFG, 1, SMAX)))).shape[-1]
    per_slot = [arg((SLOTS,) + tail, dtype) for tail, dtype in (
        ((), jnp.int32), ((vocab,), jnp.float32), ((2,), jnp.uint32),
        ((), jnp.bool_), ((), jnp.float32), ((), jnp.bool_))]
    if program == "admit_apart":
        fam = dataclasses.replace(fam, pool_rows=False)
    return jax.jit(admission(fam, CFG, SMAX, None),
                   donate_argnums=(1, 3)).lower(
        params, pool, *per_slot, arg((SMAX // CHUNK, CHUNK), jnp.int32),
        arg((7,), jnp.int32), arg((2,), jnp.uint32))


def _but_for_metadata(text: str) -> str:
    text = re.sub(r",? ?metadata=\{[^{}]*\}", "", text)
    lines = text.splitlines()
    first = next(i for i, line in enumerate(lines)
                 if re.match(r"^(ENTRY )?%\S+ \(", line))
    names = {}
    number = lambda m: names.setdefault(m.group(0), f"%v{len(names)}")
    return re.sub(r"%[\w.\-]+", number, "\n".join(lines[:1] + lines[first:]))


def _compiled(program, without=()):
    enter = source_info_util.ExtendNameStackContextManager.__enter__

    def skipping(self):
        if self.name in without:
            self.prev = source_info_util._source_info_context.context
            return self.prev.name_stack
        return enter(self)
    cls = source_info_util.ExtendNameStackContextManager
    cls.__enter__ = skipping
    try:
        return _lowered(program).compile().as_text()
    finally:
        cls.__enter__ = enter


@pytest.fixture(scope="module")
def with_scopes():
    return {p: _compiled(p) for p in PRESENT}


@pytest.mark.parametrize("program", list(PRESENT))
@pytest.mark.parametrize("without", [tuple(SCOPES)] + [(s,) for s in SCOPES],
                         ids=["all"] + list(SCOPES))
def test_a_scope_changes_nothing_but_metadata(with_scopes, program, without):
    text = with_scopes[program]
    named = lambda scope, hlo: re.search(
        rf'op_name="[^"]*[/;]{scope}/', hlo) is not None
    for scope in without:
        assert named(scope, text) == (scope in PRESENT[program]), scope
    bare = _compiled(program, without)
    assert not any(named(scope, bare) for scope in without)
    assert _but_for_metadata(bare) == _but_for_metadata(text)
