"""A model family is one declaration (``gpt_inference.Family``): what
``models.cache_family`` returns for every served configuration, that the
object's passes are ``gpt_inference``'s with the family in them, that every
refusal a user can meet reads as it always has, that the sweep's plan
(``decode_attention.sweep_plan``) is the block of the work list and of the
kernel, and that ``serving/`` asks the family and nothing below it."""

import ast
import dataclasses
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from benchmarks.chip import (hybrid_ssm_moe_family, latent_moe_family,
                             mellum_family, nemotron_h_family)
from benchmarks.chip import dots3_family, kimi_linear_family
from benchmarks.chip import lfm2_family, longcat_flash_family
from benchmarks.chip import solar_open2_family
from deepspeed_tpu.models import (cache_family, conv_moe, conv_moe_inference,
                                  linear_gqa_moe,
                                  gpt, gpt_inference, gpt_moe,
                                  gpt_moe_inference, hybrid_ssm_moe,
                                  hybrid_ssm_moe_inference,
                                  latent_moe_inference,
                                  linear_gqa_moe_inference,
                                  linear_latent_moe_inference,
                                  shortcut_latent_moe_inference,
                                  sparse_latent_moe_inference, window_moe,
                                  window_moe_inference)
from deepspeed_tpu.ops.pallas import decode_attention
from tests.unit.models.family_harness import tiny_file
from tests.unit.ops.traced_sweeps import sweep_calls

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
DENSE_CFG = gpt.GPTConfig(vocab_size=128, max_seq_len=64, n_layer=2,
                          n_head=2, d_model=32, dtype=jnp.float32,
                          vocab_round_to=128)
MOE_CFG = gpt_moe.GPTMoEConfig(vocab_size=128, max_seq_len=64, n_layer=2,
                               n_head=2, d_model=32, dtype=jnp.float32,
                               vocab_round_to=128, num_experts=2)


#: the served configurations: class -> (config, params' init, family)
def _served(name):
    if name == "dense":
        return DENSE_CFG, lambda k: gpt.init(DENSE_CFG, k), \
            gpt_inference.DENSE
    if name == "moe":
        return MOE_CFG, lambda k: gpt_moe.init(MOE_CFG, k), \
            gpt_moe_inference.FAMILY
    builder, file, family = {
        "latent": (latent_moe_family, "kimi-k2.7-code-ep32",
                   latent_moe_inference.FAMILY),
        "hybrid": (hybrid_ssm_moe_family, "granite-4.0-h-small-ep4",
                   hybrid_ssm_moe_inference.FAMILY),
        "single_part": (nemotron_h_family, "nemotron-3-nano-30b-a3b-ep4",
                        hybrid_ssm_moe_inference.FAMILY),
        "window": (mellum_family, "mellum2-12b-a2.5b-ep4",
                   window_moe_inference.FAMILY),
        "selected": (dots3_family, "dots3-note-prev-ep32",
                     sparse_latent_moe_inference.FAMILY),
        "linear": (kimi_linear_family, "kimi-linear-48b-a3b-ep8",
                   linear_latent_moe_inference.FAMILY),
        "shortcut": (longcat_flash_family, "longcat-flash-chat-ep32",
                     shortcut_latent_moe_inference.FAMILY),
        "conv": (lfm2_family, "lfm2-8b-a1b",
                 conv_moe_inference.FAMILY),
        "linear_gqa": (solar_open2_family, "solar-open2-250b-ep8",
                       linear_gqa_moe_inference.FAMILY)}[name]
    cfg = dataclasses.replace(builder.build(tiny_file(file)),
                              dtype=jnp.float32)
    return cfg, lambda k: builder.init(cfg, k, jnp.float32), family


SERVED = ("dense", "moe", "latent", "hybrid", "single_part", "window",
          "selected", "linear", "shortcut", "conv", "linear_gqa")


@pytest.mark.parametrize("name", SERVED)
def test_cache_family_returns_the_whole_declaration(name):
    cfg, _, family = _served(name)
    fam = cache_family(cfg)
    assert fam is family and isinstance(fam, gpt_inference.Family)
    for field in dataclasses.fields(fam):
        value = getattr(fam, field.name)
        if field.name == "prompt_pass":      # GPT-MoE's one real override
            assert (value is not None) == (name == "moe")
        elif field.name == "chunk_form":
            # the families whose latent chunk passes have two forms (the
            # selecting one's under their biases too)
            assert (value is not None) == (name in (
                "latent", "linear", "shortcut", "selected"))
        elif field.name == "pool_rows":
            # the families whose attention reads a row of a pool where it
            # lies (``attend_cached(row=)``): the dense block's own hook
            assert value == (name in ("dense", "moe"))
            assert value == (
                fam.attend_cached is gpt_inference._dense_attend_cached)
        elif field.name in ("unsupported", "state_counters",
                            "select_counters"):
            assert value is not None
        else:
            assert callable(value), field.name
    assert set(fam.stats_groups(cfg)) == {
        "dense": set(), "moe": set(), "latent": {"moe_pairs"},
        "hybrid": {"moe_pairs", "state_steps"},
        "single_part": {"moe_pairs", "state_steps"},
        "window": {"moe_pairs"},
        "selected": {"moe_pairs", "sparse_select"},
        "linear": {"moe_pairs", "state_steps"},
        "shortcut": {"moe_pairs"},
        "conv": {"moe_pairs", "state_steps"},
        "linear_gqa": {"moe_pairs", "state_steps"}}[name]
    assert fam.select_counters == (
        sparse_latent_moe_inference.SELECT_COUNTERS
        if name == "selected" else ())
    assert fam.state_counters == (
        hybrid_ssm_moe_inference.STATE_COUNTERS
        if name in ("hybrid", "single_part", "linear", "linear_gqa") else
        ("conv_rows_stepped", "conv_tokens_real", "conv_tokens_padded")
        if name == "conv" else ())
    # the per-slot state is a tuple of as many arrays as the family says:
    # a pair for the scans' families, ONE for the convolution's tail
    state = jax.eval_shape(lambda: fam.init_cache(cfg, 2, 32)).state
    assert (None if state is None else len(state)) == {
        "hybrid": 2, "single_part": 2, "linear": 2, "conv": 1,
        "linear_gqa": 2}.get(name)
    # the dense family alone serves as a draft
    assert ("draft" in fam.unsupported) == (name != "dense")


@pytest.mark.parametrize("name", SERVED)
def test_the_objects_passes_are_gpt_inferences_with_the_family_in_them(name):
    """``prefill ; extend ; decode_step`` through the object trace to what
    the same calls through ``gpt_inference.*(family=...)`` trace to: the
    same program, so the same numbers."""
    cfg, init, fam = _served(name)
    params = jax.eval_shape(init, jax.random.PRNGKey(0))
    B, S, smax = 2, 16, 64
    cache = jax.eval_shape(lambda: fam.init_cache(cfg, B, smax))
    tokens = jax.ShapeDtypeStruct((B, S), jnp.int32)
    rows = jax.ShapeDtypeStruct((B,), jnp.int32)
    live = jax.ShapeDtypeStruct((B,), jnp.bool_)

    def through(f):
        def run(p, c, t, lengths, active):
            _, c = f.prefill(p, t, cfg, c, valid=lengths)
            lg, c = f.extend(p, t, cfg, c, lengths=lengths, valid=lengths)
            return lg, f.decode_step(p, t[:, 0], cfg, c, lengths=lengths,
                                     active=active)
        return str(jax.make_jaxpr(run)(params, cache, tokens, rows, live))

    class Module:
        """``gpt_inference``'s functions with ``family=`` handed in."""
        @staticmethod
        def prefill(p, t, cfg, c, valid=None):
            return gpt_inference.prefill(p, t, cfg, c, family=fam,
                                         valid=valid)

        @staticmethod
        def extend(p, t, cfg, c, lengths=None, valid=None):
            return gpt_inference.extend(p, t, cfg, c, lengths=lengths,
                                        family=fam, valid=valid)

        @staticmethod
        def decode_step(p, t, cfg, c, lengths=None, active=None):
            return gpt_inference.decode_step(p, t, cfg, c, lengths=lengths,
                                             active=active, family=fam)

    assert through(fam) == through(Module)


def test_the_object_computes_what_the_module_computes():
    """The traced equality above, once in numbers (the dense family)."""
    params = gpt.init(DENSE_CFG, jax.random.PRNGKey(0))
    fam = cache_family(DENSE_CFG)
    tokens = jnp.arange(12, dtype=jnp.int32)[None] % DENSE_CFG.vocab_size
    got, want = [], []
    for out, (prefill, extend, step) in (
            (got, (fam.prefill, fam.extend, fam.decode_step)),
            (want, (gpt_inference.prefill, gpt_inference.extend,
                    gpt_inference.decode_step))):
        lg, c = prefill(params, tokens[:, :8], DENSE_CFG,
                        fam.init_cache(DENSE_CFG, 1, 32))
        out.append(lg)
        lg, c = extend(params, tokens[:, 8:], DENSE_CFG, c)
        out.append(lg)
        out.append(step(params, tokens[:, 0], DENSE_CFG, c)[0])
    for a, b in zip(got, want):
        assert (np.asarray(a) == np.asarray(b)).all()


# ------------------------------------------------------------- refusals
#
# Every refusal a user could meet, with the words it used: the batcher's
# (``serving.<feature> with <Config>: <why>``) are
# ``test_family_conformance.py``'s, a case a family and feature; here what
# the two GPT families serve, the int8 cache's refusal where the cache is
# made, and the two draft refusals.

def _shell_batcher(name):
    """A batcher that holds a family and a config and nothing else: what
    ``unsupported`` and ``refuse`` read."""
    from deepspeed_tpu.serving.batcher import SlotBatcher
    cfg, _, fam = _served(name)
    b = object.__new__(SlotBatcher)
    b._fam, b._cfg = fam, cfg
    return b


@pytest.mark.parametrize("name", ["dense", "moe"])
@pytest.mark.parametrize("feature", ["speculative", "paging", "prefix"])
def test_what_a_family_serves_is_not_refused(name, feature):
    b = _shell_batcher(name)
    assert b.unsupported(feature) is None
    b.refuse(feature)
    if name == "dense":
        assert not b._fam.unsupported


@pytest.mark.parametrize("name,said", [
    ("latent", "the latent-attention family caches in the compute dtype "
               "only: the int8 cache's scale banks are per head and a latent "
               "row has no heads (kv_cache_dtype='int8')"),
    ("hybrid", "the hybrid state-space family caches in the compute dtype "
               "only (kv_cache_dtype='int8')"),
    ("single_part", "the hybrid state-space family caches in the compute "
                    "dtype only (kv_cache_dtype='int8')"),
    ("window", "the window-and-full family caches in the compute dtype "
               "only (kv_cache_dtype='int8')"),
    ("linear", "the linear-attention family caches in the compute dtype "
               "only: the int8 cache's scale banks are per head, a latent "
               "row has no heads and the state is float32 "
               "(kv_cache_dtype='int8')"),
    ("shortcut", "the latent-attention families cache in the compute dtype "
                 "only: the int8 cache's scale banks are per head and a "
                 "latent row has no heads (kv_cache_dtype='int8')"),
    ("conv", "the short-convolution family caches in the compute dtype only "
             "(kv_cache_dtype='int8')"),
    ("linear_gqa", "the linear-attention families cache in the compute dtype "
                   "only: the state is float32 and has no heads' scale banks "
                   "(kv_cache_dtype='int8')"),
])
def test_the_int8_cache_is_refused_where_the_cache_is_made(name, said):
    cfg, _, fam = _served(name)
    with pytest.raises(NotImplementedError) as e:
        fam.init_cache(cfg, 1, 32, kv_dtype="int8")
    assert str(e.value) == said
    assert fam.init_cache(cfg, 1, 32).k.dtype == jnp.float32


@pytest.fixture(scope="module")
def dense_engine():
    return deepspeed_tpu.init_inference(
        model=(DENSE_CFG, gpt.init(DENSE_CFG, jax.random.PRNGKey(0))),
        config={"dtype": "float32"})


def _draft_engine(name):
    cfg, init, _ = _served(name)
    if name == "moe":   # a draft shares the target's vocabulary
        assert cfg.vocab_size == DENSE_CFG.vocab_size
    # drawn in one program (op by op every initializer compiles on its own)
    return deepspeed_tpu.init_inference(
        model=(cfg, jax.jit(init)(jax.random.PRNGKey(1))),
        config={"dtype": "float32"})


@pytest.mark.parametrize("name", ["moe", "latent", "hybrid", "linear",
                                  "shortcut", "conv", "linear_gqa"])
def test_a_draft_must_be_dense_and_both_callers_say_so(dense_engine, name):
    draft = _draft_engine(name)
    with pytest.raises(NotImplementedError) as e:
        dense_engine.generate_speculative(jnp.zeros((1, 4), jnp.int32),
                                          draft, max_new_tokens=2)
    assert str(e.value) == "the draft must be a dense GPT-family engine"
    with pytest.raises(NotImplementedError) as e:
        dense_engine.serve(
            config={"slots": 2, "max_len": 32, "prefill_chunk": 16,
                    "speculative": {"enabled": True, "draft_k": 2}},
            draft=draft)
    assert str(e.value) == \
        "the serving draft must be a dense GPT-family engine"


# ----------------------------------------------------------- the sweep

M, E, A = "mamba", "experts", "attention"
_HYBRID = dict(vocab_size=256, d_model=64, ssm_heads=4, ssm_head_dim=32,
               ssm_state=16, ssm_chunk=16, n_experts=4, experts_per_token=2,
               d_expert=32, d_shared=32, dtype=jnp.float32)


def _swept(name):
    """``(model, config, slot length, sweep or None)`` of rows of every
    kind the three sweeps serve, and of one no sweep does."""
    if name == "latent":
        cfg = dataclasses.replace(_served("latent")[0], max_seq_len=1024)
        return latent_moe_family, cfg, 1024, decode_attention.LATENT_SWEEP
    small = dict(vocab_size=256, n_layer=2, n_head=4, d_model=128,
                 max_seq_len=1024, dtype=jnp.float32)
    if name == "dense":
        return gpt, gpt.GPTConfig(**small), 1024, \
            decode_attention.DENSE_SWEEP
    if name == "dense-banded":
        return gpt, gpt.GPTConfig(local_attention_window=64,
                                  local_attention_alternating=True,
                                  **small), \
            1024, decode_attention.DENSE_SWEEP
    if name == "moe":
        return gpt_moe, gpt_moe.GPTMoEConfig(num_experts=2, **small), 1024, \
            decode_attention.DENSE_SWEEP
    if name == "hybrid":
        return hybrid_ssm_moe, hybrid_ssm_moe.HybridSSMMoEConfig(
            max_seq_len=1024, layer_types=(M, A, M), n_head=16, n_kv_head=8,
            head_dim=128, **_HYBRID), 1024, decode_attention.GROUPED_SWEEP
    if name == "single_part":
        return hybrid_ssm_moe, hybrid_ssm_moe.HybridSSMMoEConfig(
            max_seq_len=1024, layer_types=(M, E, A, E, M, A, E), n_head=8,
            n_kv_head=2, head_dim=128, mixer_ffn=False, **_HYBRID), 1024, \
            decode_attention.GROUPED_SWEEP
    if name == "window":        # two pools: whole rows and rings of 512
        return window_moe, window_moe.WindowMoEConfig(
            vocab_size=256, max_seq_len=1024, layer_types=("window", "full",
                                                           "window"),
            d_model=128, n_head=8, n_kv_head=2, head_dim=128, window=512,
            n_experts=4, experts_per_token=2, d_expert=32,
            dtype=jnp.float32), 1024, decode_attention.GROUPED_SWEEP
    if name == "conv":      # heads of 64 in a row of whole lane rows
        return conv_moe, conv_moe.ConvMoEConfig(
            vocab_size=256, max_seq_len=1024, layer_types=("conv", "conv",
                                                           A, "conv", A),
            d_model=128, n_head=8, n_kv_head=2, head_dim=64, n_experts=4,
            experts_per_token=2, d_expert=32, d_ff=64,
            dtype=jnp.float32), 1024, decode_attention.GROUPED_SWEEP
    if name == "linear_gqa":    # a gated grouped row beside a delta-rule state
        return linear_gqa_moe, linear_gqa_moe.LinearGQAMoEConfig(
            vocab_size=256, max_seq_len=1024, n_layer=4, gqa_layers=(0,),
            d_model=128, kda_heads=2, kda_head_dim=32, kda_chunk=16,
            n_head=16, n_kv_head=2, head_dim=128, n_experts=4,
            experts_per_token=2, d_expert=32,
            dtype=jnp.float32), 1024, decode_attention.GROUPED_SWEEP
    if name == "grouped-64":    # the same row under another family's heads
        return hybrid_ssm_moe, hybrid_ssm_moe.HybridSSMMoEConfig(
            max_seq_len=1024, layer_types=(M, A, M), n_head=4, n_kv_head=2,
            head_dim=64, **_HYBRID), 1024, decode_attention.GROUPED_SWEEP
    assert name == "grouped-32"     # grouped heads of a quarter lane row
    return hybrid_ssm_moe, hybrid_ssm_moe.HybridSSMMoEConfig(
        max_seq_len=1024, layer_types=(M, A, M), n_head=4, n_kv_head=2,
        head_dim=32, **_HYBRID), 1024, None


@pytest.mark.parametrize("name", ["dense", "dense-banded", "moe", "latent",
                                  "hybrid", "single_part", "window",
                                  "conv", "linear_gqa", "grouped-64",
                                  "grouped-32"])
def test_the_plan_is_the_work_lists_block_and_the_kernels(monkeypatch, name):
    """One function of the row says which sweep serves it and by which
    block; the family's plan, the tick's work list and the kernel the tick
    launches all have it from there."""
    monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")
    model, cfg, smax, kernel = _swept(name)
    fam = cache_family(cfg)
    slots = 3
    plan = fam.sweep_plan(cfg, smax, jnp.dtype(cfg.dtype).itemsize)
    row = gpt_inference.cache_row(cfg)
    ring = plan.ring
    assert plan._replace(ring=None) == decode_attention.sweep_plan(
        row, smax, cfg.n_head,
        kv_heads=getattr(cfg, "n_kv_head", None) if len(row) == 2 else None,
        itemsize=4, windows=plan.windows)
    assert (ring is not None) == (name == "window")
    assert plan.kernel == kernel and plan.Smax == smax
    assert plan.block_k == (
        decode_attention.latent_block_k(smax) if len(row) == 1
        else decode_attention.decode_block_k(smax, row[0]))
    assert (plan.copy_rows is not None) == \
        (kernel == decode_attention.DENSE_SWEEP)
    assert sum(n for _, n in plan.windows) == \
        gpt_inference.cache_layers(cfg)
    assert len(plan.windows) == (2 if name == "dense-banded" else 1)
    if model is latent_moe_family:
        params = jax.eval_shape(
            lambda: model.init(cfg, jax.random.PRNGKey(0), jnp.float32))
    else:
        params = jax.eval_shape(
            lambda: model.init(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: fam.init_cache(cfg, slots, smax))
    rows = jax.ShapeDtypeStruct((slots,), jnp.int32)
    tick = jax.make_jaxpr(
        lambda p, c, tok, lengths, active: fam.decode_step(
            p, tok, cfg, c, lengths=lengths, active=active))(
        params, cache, rows, rows, jax.ShapeDtypeStruct((slots,), jnp.bool_))
    calls = sweep_calls(tick.jaxpr, slots, smax, row[0])
    if kernel is None:
        assert not calls
        return
    lists = {(kernel, plan.block_k, plan.block_k)}
    if ring is not None:    # a ring's list is its own pool's (``sweep_calls``
        # reads a list's length as if it covered ``smax``)
        assert ring == decode_attention.sweep_plan(
            row, 512, cfg.n_head, kv_heads=cfg.n_kv_head, itemsize=4,
            windows=((None, 2),))
        lists.add((kernel, ring.block_k, ring.block_k * smax // ring.Smax))
    assert calls and set(calls) == lists, calls


def test_the_plans_counts_are_the_kernel_files():
    plan = decode_attention.sweep_plan((1024, 1024), 1024, 16, itemsize=2,
                                       windows=((None, 3), (256, 1)))
    frontiers = [5, 300, 1023]
    assert plan.block_counts(frontiers, 8) == \
        decode_attention.sweep_block_counts(frontiers, 8, 1024, 256,
                                            plan.windows)
    assert plan.token_counts(frontiers) == \
        decode_attention.sweep_token_counts(frontiers, 1024, 256,
                                            plan.windows, 16)
    # a slot length no block tiles: no sweep, nothing counted
    none = decode_attention.sweep_plan((128, 128), 96, 2)
    assert none.kernel is None and none.block_k is None
    assert none.block_counts([10], 4) == none.token_counts([10]) == (0, 0)


# ------------------------------------------------------------ the seam

def _serving_sources():
    root = os.path.join(ROOT, "deepspeed_tpu", "serving")
    for name in sorted(os.listdir(root)):
        if name.endswith(".py"):
            with open(os.path.join(root, name)) as f:
                yield name, ast.parse(f.read())


def test_serving_asks_the_family_and_nothing_below_it():
    """No module of ``serving/`` imports from ``ops.pallas`` (which kernel
    serves a row is the kernel file's to say, through the family's plan),
    and none probes a family with ``getattr(fam, name, default)``: a
    ``Family`` has every field."""
    seen = 0
    for name, tree in _serving_sources():
        seen += 1
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom):
                assert "ops" not in (node.module or "").split("."), \
                    (name, node.lineno)
                assert not any(a.name in ("ops", "pallas")
                               for a in node.names), (name, node.lineno)
            if isinstance(node, ast.Import):
                assert not any(".ops" in a.name for a in node.names), \
                    (name, node.lineno)
            if isinstance(node, ast.Call) and \
                    getattr(node.func, "id", None) == "getattr" and \
                    len(node.args) == 3:
                probed = ast.unparse(node.args[0])
                assert "fam" not in probed.lower(), (name, node.lineno)
    assert seen >= 8
