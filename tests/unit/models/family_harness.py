"""What the tests of every expert family share, said once: where a
configuration's files are, how its tiny model is drawn and served, how the
plain reference is run, and ``SPECS``, one row a family.

A new family's tests start HERE: add a row to ``SPECS`` (its configuration's
name, its ``benchmarks.chip`` modules, its tolerance and why, the variants of
each probe it runs) and a tiny configuration under
``tests/unit/chipbench/tiny/configs/``; ``test_family_conformance.py`` then
asks it every question it asks the others.  ``test_<family>.py`` holds only
what that family has and no other.

Everything expensive is cached a process: a model is drawn once a
``(configuration, keys, seed, scale, dtype)`` in ONE jitted program, a
gateway is built once a ``(model, serving)``, and the reference always runs
under ``jax.jit`` (op by op it compiles every primitive of every new shape
on its own: PERF.md 6, PR 57)."""

import contextlib
import dataclasses
import functools
import json
import os
from typing import Any, Callable, Optional, Tuple

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from benchmarks.chip import (dots3_family, hybrid_ssm_moe_family,
                             kimi_linear_family, latent_moe_family,
                             lfm2_family, longcat_flash_family, mellum_family,
                             nemotron_h_family, solar_open2_family)
from benchmarks.chip.reference import (dots3_control, dots3_reference,
                                       hybrid_ssm_moe_control,
                                       hybrid_ssm_moe_reference,
                                       kimi_linear_control,
                                       kimi_linear_reference,
                                       latent_moe_control,
                                       latent_moe_reference, lfm2_control,
                                       lfm2_reference,
                                       longcat_flash_control,
                                       longcat_flash_reference,
                                       mellum_control, mellum_reference,
                                       nemotron_h_control,
                                       nemotron_h_reference,
                                       solar_open2_control,
                                       solar_open2_reference)
from deepspeed_tpu.models import (cache_family, conv_moe, hybrid_ssm_moe,
                                  hybrid_ssm_moe_inference, latent_moe,
                                  linear_gqa_moe, linear_latent_moe,
                                  shortcut_latent_moe,
                                  sparse_latent_moe, window_moe)
from deepspeed_tpu.models.hybrid_ssm_moe import run_parts
from deepspeed_tpu.moe.held_experts import n_pair_counts, read_pair_counts

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
#: the one geometry every probe serves at (a row of ``SPECS`` may override)
SERVING = {"slots": 6, "max_len": 128, "prefill_chunk": 16,
           "queue_capacity": 8}
CHUNK = SERVING["prefill_chunk"]
TICKS = 8
#: the scale the fault probes draw at: ten times the families' 0.02, so that
#: the layers and not the embedding make the logits (at 0.02 and these
#: widths a layer adds a thousandth to a logit, and so does a fault in it)
LOUD = 0.2
#: both sides compute in float32; the program's chunked or blocked sums run
#: in another order than the reference's and at the CPU's default precision
ATOL, RTOL = 2e-5, 1e-4


def _read(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def published(name):
    """The benchmark's configuration file as the cell runs it."""
    return _read("benchmarks", "chip", "configs", name + ".json")


def tiny_file(name, **keys):
    """The benchmark's file under the rehearsal's tiny sizes."""
    return {**published(name),
            **_read("tests", "unit", "chipbench", "tiny", "configs",
                    name + ".json"), **keys}


def entry(name):
    """The configuration's entry of ``BENCHMARK.json``."""
    return next(c for c in _read("BENCHMARK.json")["configs"]
                if c["name"] == name)


def _loud_router_bias(params):
    """A selection bias large enough to move choices at the tiny size."""
    for run in params["runs"]:
        for part in run_parts(run):
            if "router_bias" in part:
                part["router_bias"] = part["router_bias"] * 30
    return params


@dataclasses.dataclass(frozen=True)
class Shares:
    """How a family's expert layer is cut: ``keys`` make the tiny file hold
    every expert, ``layer(params)`` is one expert layer's parameters,
    ``ffn(x, p, cfg)`` the program's layer ``-> (out, counts)``,
    ``matrices`` the routed stacks (the last is the down-projection),
    ``shares`` x ``each`` the cut, ``uncut(cfg)`` the config that holds all
    where the tiny file holds a share, ``reference(file, x, p)`` the plain
    reference's uncut layer where it has one apart, ``loud`` weights drawn
    at ``LOUD``, ``atol`` / ``rtol`` what the shares' sum is held to against
    the uncut layer (the same products in the same order but for the cut:
    1e-6 / 1e-5 where the family's own scales are drawn)."""
    keys: dict
    layer: Callable
    ffn: Callable
    matrices: Tuple[str, ...] = ("w_gu", "w_down")
    shares: int = 4
    each: int = 4
    uncut: Optional[Callable] = None
    reference: Optional[Callable] = None
    loud: bool = False
    atol: float = ATOL
    rtol: float = RTOL


@dataclasses.dataclass(frozen=True)
class Spec:
    """One family's row: see the module docstring."""
    name: str                       # the configuration, and the case's id
    family: Any                     # benchmarks.chip.<x>_family
    reference: Any                  # benchmarks.chip.reference.<x>_reference
    program: Any                    # deepspeed_tpu.models.<x>
    #: fault -> weights with it planted, and the context that plants the rest
    weights: dict = dataclasses.field(default_factory=dict)
    planted: Callable = lambda fault: contextlib.nullcontext()
    #: why ATOL / RTOL hold for this family (what reads under them here)
    why: str = ""
    #: ``cfg -> cfg`` and ``params -> params`` for the tiny model
    tweak: Callable = lambda cfg: cfg
    retouch: Callable = lambda params: params
    #: the slot path's probes: ``(id, prompt lengths, marks)``.  ``apply``,
    #: the slot path and the faults read ONE model a family, drawn at ``LOUD``
    #: from ``fault_seed`` over ``keys`` of the tiny file: one draw, one
    #: gateway and one compiled reference a process
    slot_paths: Tuple = ()
    keys: dict = dataclasses.field(default_factory=dict)
    #: ``cfg -> layers`` that keep a state (``state_steps``) and that route
    #: (None: the routed count is not predicted); the names the family gives
    #: that group's three counters
    state_layers: Optional[Callable] = None
    state_counters: Tuple[str, ...] = \
        hybrid_ssm_moe_inference.STATE_COUNTERS
    routed_layers: Optional[Callable] = None
    zero_experts: bool = False
    #: ``apply`` against the reference: tokens a row, marks
    apply_tokens: int = 40
    apply_marks: Tuple = ()
    #: ``(fault, "apply" | "slot", marks)``: each reads over ``fault_over``
    #: times ATOL where the sound run reads under ATOL; but ``faint`` ones,
    #: which need only reach the slot path's logits (a state kept in bf16
    #: shows over a reply's length, not over 8 ticks)
    faults: Tuple = ()
    fault_over: float = 50.0
    fault_seed: int = 1
    faint: Tuple[str, ...] = ()
    #: faults also held to ``not compare.agrees``, the comparison that
    #: decides ``correct`` (and the sound run to ``compare.agrees``)
    decided: Tuple[str, ...] = ()
    #: ``(module, faults)``: the ``readings`` the configuration's control
    #: runs (the script that sets the cell's limits on the chip) at the tiny
    #: size, a sound run and these faults (20 s each: two a control)
    readings: Tuple = ()
    shares: Optional[Shares] = None
    #: ``(key, value, said)``: a sibling's key the builder refuses by name,
    #: on the published file (or the tiny one)
    siblings: Tuple = ()
    siblings_on_tiny: bool = False
    #: where the cell's bf16 is judged here by ``compare.py``'s limits:
    #: through ``"apply"`` or through the ``"slot"`` path (None: nowhere)
    bf16: Optional[str] = None
    #: the batcher's words where a test has always pinned them
    said: dict = dataclasses.field(default_factory=dict)


# ------------------------------------------------------------- the models

def _frozen(keys):
    return json.dumps(keys, sort_keys=True)


@functools.lru_cache(maxsize=None)
def _model(name, keys, seed, std, dtype, weights="float32"):
    spec, file = SPECS[name], tiny_file(name, **json.loads(keys))
    cfg = spec.tweak(dataclasses.replace(spec.family.build(file),
                                         dtype=jnp.dtype(dtype)))

    def draw(key):
        if std is None:
            params = spec.family.init(cfg, key, jnp.dtype(weights))
        else:
            params = spec.program.init(
                dataclasses.replace(cfg, param_dtype=jnp.float32), key,
                std=std)
        return spec.retouch(params)
    return cfg, jax.jit(draw)(jax.random.PRNGKey(seed))


def model(spec, seed=0, std=None, dtype=jnp.float32, weights=jnp.float32,
          **keys):
    """``(config, weights)`` of ``tiny_file(spec.name, **keys)``: the
    family's own scales (served in ``weights``), or every matrix at
    ``std``."""
    return _model(spec.name, _frozen(keys), seed, std, jnp.dtype(dtype).name,
                  jnp.dtype(weights).name)


def engine(cfg, params):
    return deepspeed_tpu.init_inference(
        model=(cfg, params), config={"dtype": jnp.dtype(cfg.dtype).name})


def gateway(cfg, params, **serving):
    """A stopped gateway over the model at ``SERVING``: ``probe_logits``
    drives its own programs and slot cache."""
    served = engine(cfg, params).serve(config={**SERVING, **serving})
    served.shutdown(drain=False, timeout=60)
    return served


@contextlib.contextmanager
def over(served, cfg, weights):
    """``served`` running its compiled programs over other weights of the
    same shapes (the batcher hands its engine's to every launch)."""
    held = served._batcher._engine
    kept, held.params = held.params, engine(cfg, weights).params
    try:
        yield served
    finally:
        held.params = kept


@functools.lru_cache(maxsize=None)
def _served(name, keys, seed, std, dtype, serving):
    return gateway(*_model(name, keys, seed, std, dtype),
                   **json.loads(serving))


def served(spec, seed=0, std=None, dtype=jnp.float32, keys=None, **serving):
    """:func:`gateway` over :func:`model`, one a process: its programs
    compile once, and a probe reads its cumulative counters by difference."""
    return _served(spec.name, _frozen(keys or {}), seed, std,
                   jnp.dtype(dtype).name, _frozen(serving))


def tokens(cfg, n=40):
    return jax.random.randint(jax.random.PRNGKey(2), (2, n), 0,
                              cfg.vocab_size)


def prompts(cfg, lengths):
    return [np.random.default_rng(3 + n).integers(
        0, cfg.vocab_size, n).astype(np.int32) for n in lengths]


# ---------------------------------------------------------- the reference

@functools.lru_cache(maxsize=None)
def _forward(name, keys, last):
    forward = SPECS[name].reference.forward
    file = tiny_file(name, **json.loads(keys))
    return jax.jit(lambda params, toks: forward(file, params, toks, last))


def reference_logits(spec, params, toks, last, **keys):
    """The plain reference's logits of the last ``last`` positions, always
    compiled, the program cached a ``(configuration, keys, last)``."""
    return np.asarray(_forward(spec.name, _frozen(keys), last)(
        params, jnp.asarray(toks)))


def slot_path_logits(spec, served, params, lengths, ticks=TICKS, **keys):
    """``[(got, reference)]`` a prompt: chunked prefill, then ``ticks``
    greedy ticks through the gateway's own programs and slot cache, against
    the reference's full forward over the prompt and what was replied."""
    asked = prompts(served._batcher._cfg, lengths)
    replies, got = served.probe_logits(asked, ticks)
    vocab = served._batcher._cfg.vocab_size
    return [(np.asarray(logits)[:, :vocab], reference_logits(
        spec, params, np.concatenate([p, np.asarray(reply, np.int32)])[None],
        ticks + 1, **keys)[0])
        for p, reply, logits in zip(asked, replies, got)]


def off(got, ref, rtol=RTOL):
    """The largest departure beyond ``rtol``: held against an ``atol``."""
    return float((np.abs(got - ref) - rtol * np.abs(ref)).max())


def counters(served):
    """The batcher's cumulative counter groups, as arrays (None: no such
    group in this family)."""
    b = served._batcher
    return {g: None if b.counts(g) is None else np.array(b.counts(g))
            for g in ("moe_pairs", "state_steps", "sparse_select")}


def grown(served, before, group):
    now = counters(served)[group]
    return now if before[group] is None else now - before[group]


def check_counters(spec, served, before, lengths, ticks=TICKS):
    """What every family's counters say after a probe of ``lengths``."""
    b, cfg = served._batcher, served._batcher._cfg
    chunk = b.chunk
    real = sum(lengths)
    padded = sum(-(-n // chunk) * chunk for n in lengths)
    vector = grown(served, before, "moe_pairs")
    assert len(vector) == n_pair_counts(len(cfg.held))
    pairs = read_pair_counts(vector)
    assert pairs["held"] == sum(pairs["per_expert"]) > 0 \
        == pairs["pages_over_cap"]
    assert pairs["routed"] >= pairs["held"] >= pairs["visits"] > 0
    # every pair is held here only where every expert is
    assert (pairs["routed"] == pairs["held"]) == (
        len(cfg.held) == cfg.n_experts and not spec.zero_experts)
    assert (pairs["zero"] > 0) == spec.zero_experts
    if spec.routed_layers is not None:
        # every row of every call, a tick's idle slots too
        assert pairs["routed"] == (padded + ticks * b.slots) \
            * spec.routed_layers(cfg) * cfg.experts_per_token
    if spec.state_layers is not None:
        n = spec.state_layers(cfg)
        assert dict(zip(b.state_counters, grown(
            served, before, "state_steps"))) == dict(zip(
                spec.state_counters, (len(lengths) * ticks * n, real * n,
                                      (padded - real) * n)))


# ---------------------------------------------- a pass asked for no head

def passes_both_ways(fam, cfg, params, n=21, cut=8, max_len=32):
    """``{"prefill" | "extend": (logits, the family's logits of the stream,
    stream, cache, the stream's cache)}``: a ``prefill`` of ``cut`` tokens
    and the ragged ``extend`` of the ``n - cut`` after them, each as it is
    by default and asked for no head (``head=False``), in ONE program."""
    def run(p, t):
        def both(call, *args, **kw):
            lg, c = call(p, *args, **kw)
            x, cx = call(p, *args, head=False, **kw)
            return lg, fam.logits(p, x, cfg), x, c, cx
        out = {"prefill": both(fam.prefill, t[:, :cut], cfg,
                               fam.init_cache(cfg, 1, max_len))}
        out["extend"] = both(fam.extend, t[:, cut:], cfg, out["prefill"][3],
                             lengths=jnp.full((1,), cut, jnp.int32))
        return out
    return jax.jit(run)(params, tokens(cfg, n)[:1])


@functools.lru_cache(maxsize=None)
def loud_passes_both_ways(name):
    """:func:`passes_both_ways` of the family's one model at ``LOUD``."""
    cfg, params = loud(SPECS[name])
    return passes_both_ways(cache_family(cfg), cfg, params)


def check_a_pass_without_its_head(cfg, tokens_in, got):
    """What ``head=False`` promises of one pass (:func:`passes_both_ways`'s
    tuple): the stream is ``[B, S, d]`` in the compute dtype, the family's
    ``logits`` of it are the logits the pass returns by default, and the
    cache is the same cache."""
    logits, of_stream, x, cache, cache_x = got
    assert x.ndim == 3 and x.shape[:2] == tokens_in and x.dtype == cfg.dtype
    assert logits.dtype == jnp.float32 and logits.shape[:2] == tokens_in \
        and logits.shape[2] >= cfg.vocab_size
    np.testing.assert_array_equal(np.asarray(of_stream), np.asarray(logits))
    for a, b in zip(jax.tree_util.tree_leaves(cache),
                    jax.tree_util.tree_leaves(cache_x), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------- the faults

def loud(spec):
    """``(cfg, weights)``: the family's one model at ``LOUD``."""
    return model(spec, seed=spec.fault_seed, std=LOUD, **spec.keys)


def loud_served(spec):
    """The one stopped gateway over :func:`loud`."""
    return served(spec, seed=spec.fault_seed, std=LOUD, keys=spec.keys)


@functools.lru_cache(maxsize=None)
def sound_apply(name):
    """``(cfg, loud weights, tokens, reference logits)`` of ``apply`` and of
    the faults read through it: ONE forward of the reference for all."""
    spec = SPECS[name]
    cfg, params = loud(spec)
    toks = tokens(cfg, spec.apply_tokens)
    return cfg, params, toks, reference_logits(
        spec, params, toks, spec.apply_tokens, **spec.keys)


@functools.lru_cache(maxsize=None)
def _applies(name):
    """The family's ``apply`` at its loud model's config: one program a
    process for everything that replaces no function of it."""
    spec, (cfg, _) = SPECS[name], loud(SPECS[name])
    return jax.jit(lambda p, t: spec.program.apply(p, t, cfg))


@functools.lru_cache(maxsize=None)
def sound_applied(name):
    """The program's logits on :func:`sound_apply`'s model and tokens."""
    cfg, params, toks, _ = sound_apply(name)
    return np.asarray(_applies(name)(params, toks))[..., :cfg.vocab_size]


FAULT_PROMPT = (3 * CHUNK + 5,)     # three chunk edges and a padded tail


@functools.lru_cache(maxsize=None)
def sound_slot(name):
    """``(cfg, loud weights, the sound run's logits, the reference's)`` of
    the faults read through the slot path: one gateway and ONE forward of
    the reference, on the tokens the sound run replied."""
    spec = SPECS[name]
    cfg, params = loud(spec)
    (got, ref), = slot_path_logits(spec, loud_served(spec), params,
                                   FAULT_PROMPT, **spec.keys)
    return cfg, params, got, ref


def faulty_logits(spec, fault, mode):
    """``(logits with ``fault`` planted, the sound reference's)``."""
    cfg, params, *sound = (sound_apply if mode == "apply"
                           else sound_slot)(spec.name)
    weights = spec.weights.get(fault, lambda p: p)(params)
    unplanted = patchable(spec)
    with spec.planted(fault):
        # a fault that replaces no function of the program runs the sound
        # run's compiled programs on other weights
        same_program = patchable(spec) == unplanted
        if mode == "apply":
            toks, ref = sound
            run = _applies(spec.name) if same_program else jax.jit(
                lambda p, t: spec.program.apply(p, t, cfg))
            return np.asarray(run(weights, toks))[..., :cfg.vocab_size], ref
        with (over(loud_served(spec), cfg, weights) if same_program else
              contextlib.nullcontext(gateway(cfg, weights))) as faulty:
            _, got = faulty.probe_logits(prompts(cfg, FAULT_PROMPT), TICKS)
    return np.asarray(got[0])[:, :cfg.vocab_size], sound[1]


def control_readings(spec, fault):
    """``(largest, rms)`` of the control's own ``readings`` with ``fault``
    planted, at the tiny size: the check's prompts through a server's slot
    path in bf16 at the benchmark's scales, the reference on the weights as
    drawn.  A control that plants through another's ``readings`` (the
    shortcut family's, as its ``main`` does) lends it its faults for the
    call."""
    from benchmarks.chip import harness as chip
    control, _ = spec.readings
    cell = chip.Cell(
        name="tiny", chips=1, config=tiny_file(spec.name), end_to_end=[],
        per_layer=[], root=ROOT, traffic={
            "serving": {"slots": 4, "max_len": 64, "prefill_chunk": 16},
            "check": {"prompt_lens": [5, 17, 33], "ticks": 3}})
    # ``hybrid_ssm_moe_control.readings`` plants what its module's
    # ``WEIGHTS`` and ``planted`` say; the latent control's reads its own
    lent = {"WEIGHTS": spec.weights, "planted": spec.planted}
    kept = {k: getattr(control, k) for k in lent if hasattr(control, k)}
    for k in kept:
        setattr(control, k, lent[k])
    try:
        return control.readings(cell, 11, fault)
    finally:
        for k, v in kept.items():
            setattr(control, k, v)


def patchable(spec):
    """``(module, name) -> object`` of everything a control's ``planted``
    may replace: equal before and after means all was put back."""
    import importlib

    from deepspeed_tpu.models import gpt_inference
    from deepspeed_tpu.moe import held_experts
    from deepspeed_tpu.ops.pallas import decode_attention, delta_rule, ssm
    served = importlib.import_module(spec.program.__name__ + "_inference")
    return {(m.__name__, n): id(v) for m in (
        spec.program, served, latent_moe, linear_latent_moe, gpt_inference,
        held_experts, decode_attention, delta_rule, ssm)
        for n, v in vars(m).items()
        if not n.startswith("__")}


# ---------------------------------------------------------------- the rows

def _cases(*rows):
    """``(id, value, marks)`` rows, marks optional."""
    return tuple((r + ((),))[:3] for r in rows)


def _slow(*faults, mode="slot"):
    return tuple((f, mode, (pytest.mark.slow,)) for f in faults)


_first = lambda tree: jax.tree_util.tree_map(lambda a: a[0], tree)
_C, _W = CHUNK, 16          # mellum's tiny window is a chunk long

SPECS = {s.name: s for s in (
    Spec(name="kimi-k2.7-code-ep32", family=latent_moe_family,
         reference=latent_moe_reference, program=latent_moe,
         weights=latent_moe_control.FAULTS,
         why="the absorbed and the up-projected form differ in the order "
             "of their sums alone, and so do a chunked and a whole pass",
         slot_paths=_cases(("5+17+33+70", (5, 17, 33, 70))),
         # the routed product left out, a held expert's rows through another
         # expert's down-projection, a layer reading another layer's experts;
         # and 8-bit matrices through the slot path (the control's check)
         faults=(("zero", "apply", ()), ("permute", "apply", ()),
                 ("layer", "apply", ()), ("int8", "slot", ())),
         fault_seed=3, decided=("zero", "permute", "layer"),
         readings=(latent_moe_control, ("zero", "int8")),
         shares=Shares(
             keys=dict(n_routed_experts=64, num_experts_per_tok=6,
                       num_hidden_layers=2, published={
                           "num_hidden_layers": 61, "n_routed_experts": 64,
                           "vocab_size": 4096}),
             layer=lambda params: _first(params["moe_blocks"]),
             ffn=latent_moe.expert_ffn, shares=32, each=2,
             reference=lambda file, x, p:
                 latent_moe_reference._expert_layer(
                     file, x.reshape(-1, x.shape[-1]), p,
                     tuple(range(64))).reshape(x.shape)),
         bf16="apply",
         said={"speculative": "a dense draft's proposals are verified by a "
               "ragged extend this family has never been tested through",
               "paging": "parked latent rows have no re-admission test "
               "yet"}),
    Spec(name="granite-4.0-h-small-ep4", family=hybrid_ssm_moe_family,
         reference=hybrid_ssm_moe_reference, program=hybrid_ssm_moe,
         weights=hybrid_ssm_moe_control.WEIGHTS,
         planted=hybrid_ssm_moe_control.planted,
         why="the chunked scan sums a sub-chunk's terms in another order "
             "than the recurrence: 3e-7 to 4e-6 on logits of about 0.6",
         slot_paths=_cases(("1", (1,)), ("C", (_C,)), ("C+1", (_C + 1,)),
                           ("3C+5", (3 * _C + 5,))),
         state_layers=lambda cfg: cfg.count("mamba"),
         # what the program does with per-slot state through the slot path,
         # what is planted in the weights through ``apply``
         faults=tuple((f, "slot", ()) for f in (
             "chunk_edge", "pad_advance", "no_tail", "bf16_state"))
         + (("zero", "apply", ()), ("int8", "apply", ())),
         fault_over=25.0, faint=("bf16_state",),
         shares=Shares(keys=dict(num_local_experts=16),
                       layer=lambda params: _first(params["runs"][0]),
                       ffn=hybrid_ssm_moe.expert_ffn, atol=1e-6, rtol=1e-5),
         said={"speculative": "a rejected draft token would have to be "
               "rolled back out of the per-slot state, and a ragged verify "
               "pass carries no per-row count of real tokens",
               "paging": "a parked conversation's per-slot state has no "
               "block to live in: the pager moves token-indexed banks only",
               "prefix": "a pooled prefix would need a snapshot of the "
               "per-slot state at its end; the pool keeps token-indexed "
               "banks only"}),
    Spec(name="nemotron-3-nano-30b-a3b-ep4", family=nemotron_h_family,
         reference=nemotron_h_reference, program=hybrid_ssm_moe,
         weights=nemotron_h_control.WEIGHTS,
         planted=hybrid_ssm_moe_control.planted,
         why="Granite's, and for its reason: 3e-7 to 4e-6 on logits of "
             "about 0.6; the scan's chunk is 8, the gateway's 16",
         retouch=_loud_router_bias,
         slot_paths=_cases(("1", (1,)), ("Q", (8,)), ("C", (_C,)),
                           ("C+1", (_C + 1,)), ("3C+5", (3 * _C + 5,))),
         state_layers=lambda cfg: cfg.count("mamba"),
         routed_layers=lambda cfg: cfg.count("experts"),
         # H rounded to bf16 after every chunk and every tick: three chunk
         # edges and 8 ticks of rounding (the family's own departures from
         # the published mathematics are ``test_nemotron_h.py``'s)
         faults=(("bf16_state", "slot", ()),), fault_seed=0,
         shares=Shares(keys=dict(n_routed_experts=16),
                       layer=lambda params: _first(params["runs"][0][1]),
                       ffn=hybrid_ssm_moe.expert_ffn,
                       matrices=("w_up", "w_down"), atol=1e-6, rtol=1e-5),
         siblings=(
             ("n_group", 2, "no expert groups"),
             ("mlp_hidden_act", "silu", ""),
             ("tie_word_embeddings", True, "matrix of its own"),
             ("mlp_bias", True, "no bias"),
             ("moe_latent_size", 1024, "latent"),
             ("num_nextn_predict_layers", 1, "MTP"),
             ("hybrid_override_pattern", "MEMEM*EMEMEM*EMEM-", ""))),
    Spec(name="mellum2-12b-a2.5b-ep4", family=mellum_family,
         reference=mellum_reference, program=window_moe,
         weights=mellum_control.WEIGHTS, planted=mellum_control.planted,
         why="the program's softmax is blocked another way: 1e-7 to 2e-6 "
             "on logits of about 0.7",
         # a prompt shorter than the window (whose decode crosses the ring's
         # first lap at W - 3), one a window long, prompts that end inside
         # their second lap (the padded tail of their last chunk must not
         # reach the ring), and decode that crosses a lap again
         slot_paths=_cases(
             ("1", (1,)), ("short", (5,)), ("decode-laps", (_W - 3,)),
             ("W", (_W,)), ("second-lap", (_W + 9,)),
             ("decode-laps-again", (2 * _W - 4,)), ("3W+5", (3 * _W + 5,))),
         routed_layers=lambda cfg: cfg.n_layer, apply_tokens=50,
         faults=tuple((f, "slot", ()) for f in (
             "window_all", "write_first", "ring_short", "ring_one_short",
             "yarn_window", "zero")), fault_seed=8,
         shares=Shares(
             keys=dict(num_experts=16),
             layer=lambda params: {k: v[0] for k, v in
                                   params["runs"][0][0].items()},
             ffn=window_moe.expert_ffn, loud=True,
             reference=lambda file, x, p: jnp.stack([
                 mellum_reference._expert_layer(
                     file, row, p, lambda e: p["w_gu"][e],
                     lambda e: p["w_down"][e]) for row in x])),
         siblings=(
             ("model_type", "qwen3_moe", ""), ("attention_bias", True, ""),
             ("tie_word_embeddings", True, "matrix of its own"),
             ("norm_topk_prob", False, "softmax over the chosen"),
             ("mlp_layer_types", ["dense"] + ["sparse"] * 27,
              "no dense block"),
             ("layer_types", ["linear_attention"] * 28, ""))),
    Spec(name="dots3-note-prev-ep32", family=dots3_family,
         reference=dots3_reference, program=sparse_latent_moe,
         weights=dots3_control.WEIGHTS, planted=dots3_control.planted,
         why="mellum's; the selection is exact, so with both sides in "
             "float32 the same tokens are chosen: no tolerance there",
         # the tiny file: a window of 9 in a ring of 16, index_topk 24.  A
         # prompt under the window (its ring never laps), one whose decode
         # laps the ring, one whose decode crosses index_topk; slow: one
         # past it and one deep in selection whose last chunk is padded
         slot_paths=_cases(
             ("under-the-window", (4,)), ("ring-laps-in-decode", (9 + 3,)),
             ("past-topk", (24 + 9,)),
             ("selection-starts-in-decode", (24 - 4,), (pytest.mark.slow,)),
             ("deep", (70,), (pytest.mark.slow,))),
         routed_layers=lambda cfg: cfg.n_layer - 1,
         apply_tokens=50, apply_marks=(pytest.mark.slow,),
         # tier 1 holds one fault of the selection and one of the ring
         faults=(("latest", "slot", ()), ("ring_other", "slot", ()))
         + _slow("no_relu", "no_w", "no_gate", "window_512", "window_514",
                 "zero"), fault_seed=0,
         shares=Shares(
             keys={}, loud=True,
             uncut=lambda cfg: dataclasses.replace(cfg, held_experts=None),
             layer=lambda params: _first(params["runs"][1]),
             ffn=lambda x, p, cfg: sparse_latent_moe.ffn(x, p, cfg, "full"),
             reference=lambda file, x, p: jnp.stack([
                 latent_moe_reference._expert_layer(
                     {**file, "n_routed_experts": 16}, row, p,
                     tuple(range(16))) for row in x])),
         siblings=(
             ("n_group", 2, "group-limited"),
             ("rope_scaling", {"factor": 4}, "rope_scaling"),
             ("tie_word_embeddings", True, "head"),
             ("attention_bias", True, "biases"),
             ("moe_layer_freq", 2, "moe_layer_freq"),
             ("attention_gate_type", "elementwise", "gate"))),
    Spec(name="kimi-linear-48b-a3b-ep8", family=kimi_linear_family,
         reference=kimi_linear_reference, program=linear_latent_moe,
         weights=kimi_linear_control.WEIGHTS,
         planted=kimi_linear_control.planted,
         why="the chunked (WY) form sums a sub-chunk's terms in another "
             "order than the recurrence and solves its triangular system "
             "by products: 2e-7 to 5e-6 on logits of about 0.6",
         tweak=lambda cfg: dataclasses.replace(cfg, kda_chunk=8),
         retouch=_loud_router_bias,
         slot_paths=_cases(("1+C+3C+5", (1, _C, 3 * _C + 5))),
         state_layers=lambda cfg: cfg.count(linear_latent_moe.KDA),
         # through apply: the delta term left out, a head's decay the mean of
         # its channels', the convolution on k left out, the output gate left
         # out, the routed product zeroed, 8-bit matrices; through the slot
         # path the two only a cache can show
         faults=tuple((f, "apply", ()) for f in (
             "none", "no_delta", "mean_decay", "no_conv", "no_gate", "zero",
             "int8")) + (("state_other", "slot", ()),
                         ("bf16_state", "slot", ())),
         shares=Shares(keys=dict(num_experts=16),
                       layer=lambda params: _first(params["runs"][1][0]),
                       ffn=latent_moe.expert_ffn, atol=1e-6, rtol=1e-5),
         siblings=(
             ("q_lora_rank", 1536, "kimi-k2.7-code-ep32's family"),
             ("mla_use_nope", False, "kimi-k2.7-code-ep32's family"),
             ("rope_scaling", {"type": "yarn", "factor": 64},
              "rope_scaling"),
             ("num_expert_group", 8, "no expert groups"),
             ("num_nextn_predict_layers", 1, "MTP"),
             ("tie_word_embeddings", True, "matrix of its own"),
             ("moe_layer_freq", 2,
              "every layer after the leading dense ones"),
             ("model_type", "kimi_k2", "")),
         bf16="slot"),
    Spec(name="longcat-flash-chat-ep32", family=longcat_flash_family,
         reference=longcat_flash_reference, program=shortcut_latent_moe,
         weights=longcat_flash_control.WEIGHTS,
         planted=longcat_flash_control.planted,
         why="kimi-k2.7's: the absorbed and the up-projected form differ "
             "in the order of their sums alone",
         # a selection bias that is not zero, tokens whose every choice is a
         # zero-compute expert and tokens that chose none
         # (``test_shortcut_latent_moe.py`` holds that they are there)
         slot_paths=_cases(("5+33+70", (5, 33, 70))),
         keys=dict(moe_topk=2, published={
             "num_layers": 28, "n_routed_experts": 8, "vocab_size": 4096}),
         zero_experts=True,
         # one only a cache can show (a sublayer reading the other's rows)
         faults=tuple((f, "apply", ()) for f in (
             "zero", "no_identity", "permute", "shortcut_early",
             "no_kv_scale", "int8", "bf16_router"))
         + (("cache_other", "slot", ()),), fault_seed=2,
         shares=Shares(
             keys=dict(n_routed_experts=32, zero_expert_num=16, moe_topk=6,
                       published={"num_layers": 28, "n_routed_experts": 32,
                                  "vocab_size": 4096}),
             layer=lambda params: _first(params["blocks"]["moe"]),
             ffn=shortcut_latent_moe.expert_branch, each=8),
         siblings=(
             ("zero_expert_type", "copy", "zero_expert_type"),
             ("attention_method", "MHA", "attention_method"),
             ("mla_scale_kv_lora", False, "mla_scale"),
             ("rope_scaling", {"factor": 10, "rope_type": "yarn"},
              "rope_scaling"),
             ("tie_word_embeddings", True, "tie_word_embeddings"),
             ("mtp_num_layers", 1, "MTP"),
             ("norm_topk_prob", True, "norm_topk_prob"),
             ("router_bias", True, "router_bias"),
             ("attention_bias", True, "attention_bias")),
         siblings_on_tiny=True, bf16="apply",
         # a function replaced (what only a cache shows) and the weights
         readings=(hybrid_ssm_moe_control, ("cache_other", "int8"))),
    Spec(name="lfm2-8b-a1b", family=lfm2_family, reference=lfm2_reference,
         program=conv_moe, weights=lfm2_control.WEIGHTS,
         planted=lfm2_control.planted,
         why="mellum's for the attention layers; the convolution is three "
             "multiply-adds a channel in the same order on both sides, and "
             "the way back from the experts sums a token's rows in another "
             "order than the reference's loop over experts",
         retouch=_loud_router_bias,
         # one chunk, a chunk and a token, three chunk edges and a padded
         # tail: a tail carried between passes and taken inside a padded one
         slot_paths=_cases(("1+C+C+1+3C+5", (1, _C, _C + 1, 3 * _C + 5))),
         state_layers=lambda cfg: cfg.count(conv_moe.CONV),
         state_counters=("conv_rows_stepped", "conv_tokens_real",
                         "conv_tokens_padded"),
         routed_layers=lambda cfg: cfg.n_layer - cfg.n_dense,
         # through apply what is in the mathematics; through the slot path
         # the two only a carried tail can show
         faults=tuple((f, "apply", ()) for f in (
             "zero", "no_gate_c", "no_gate_b", "taps_reversed", "no_qk_norm",
             "bias_weights", "int8"))
         + (("chunk_edge", "slot", ()), ("pad_end", "slot", ()),
            ("bf16_router", "slot", ())),
         faint=("bf16_router",),
         decided=("zero", "no_gate_c", "no_gate_b", "taps_reversed"),
         readings=(hybrid_ssm_moe_control, ("pad_end", "int8")),
         siblings=(
             ("conv_bias", True, "conv_bias"),
             ("rope_scaling", {"factor": 4, "rope_type": "yarn"},
              "rope_scaling"),
             ("tie_word_embeddings", False, "untied head"),
             ("conv_L_cache", 4, "conv_L_cache"),
             ("norm_topk_prob", False, "norm_topk_prob"),
             ("use_expert_bias", False, "use_expert_bias"),
             ("layer_types", ["conv"] * 13 + ["sliding_attention"],
              "sliding_attention"),
             ("num_attention_heads", 16, "heads of 64"),
             ("model_type", "lfm2", "")),
         bf16="slot"),
    Spec(name="solar-open2-250b-ep8", family=solar_open2_family,
         reference=solar_open2_reference, program=linear_gqa_moe,
         weights=solar_open2_control.WEIGHTS,
         planted=solar_open2_control.planted,
         why="kimi-linear's for the KDA layers (the chunked form against the "
             "recurrence, its triangular system solved by halves) and "
             "granite's for the grouped layer (a blocked softmax); the gate "
             "is one multiply a channel on both sides: 1e-6 to 2e-5 on "
             "logits of about 0.6",
         tweak=lambda cfg: dataclasses.replace(cfg, kda_chunk=8),
         retouch=_loud_router_bias,
         slot_paths=_cases(("1+C+3C+5", (1, _C, 3 * _C + 5))),
         state_layers=lambda cfg: cfg.count(linear_gqa_moe.KDA),
         routed_layers=lambda cfg: cfg.n_layer,
         # through apply what is in the mathematics: beta without its factor
         # 2, the grouped layer's gate left out, its queries and keys
         # rotated, the full layer LAST in the period, the shared expert left
         # out, a head's decay the mean of its channels', the routed product
         # zeroed, 8-bit matrices; through the slot path what only a cache
         # can show
         faults=tuple((f, "apply", ()) for f in (
             "none", "beta_1", "no_attn_gate", "rotated", "full_last",
             "no_shared", "mean_decay", "zero", "int8"))
         + (("state_other", "slot", ()), ("bf16_state", "slot", ())),
         faint=("bf16_state",),
         decided=("beta_1", "no_attn_gate", "rotated", "full_last"),
         readings=(hybrid_ssm_moe_control, ("beta_1", "no_attn_gate")),
         shares=Shares(keys=dict(n_routed_experts=16),
                       layer=lambda params: _first(params["runs"][0]),
                       ffn=linear_gqa_moe.ffn, shares=8, each=2,
                       reference=lambda file, x, p: x + jax.vmap(
                           lambda h: solar_open2_reference._experts(
                               file, h, p, lambda e: p["w_gu"][e],
                               lambda e: p["w_down"][e]))(
                           solar_open2_reference._norm(
                               x, p["ln2"], file["rms_norm_eps"])),
                       atol=1e-6, rtol=1e-5),
         siblings=(
             ("first_k_dense_replace", 1, "first_k_dense_replace"),
             ("use_rope", True, "use_rope"),
             ("tie_word_embeddings", True, "tie_word_embeddings"),
             ("use_gqa_gate", False, "granite-4.0-h-small-ep4's family"),
             ("kda_use_full_proj", True, "kda_use_full_proj"),
             ("kda_allow_neg_eigval", False,
              "kimi-linear-48b-a3b-ep8's family"),
             ("n_shared_experts", 2, "n_shared_experts"),
             ("norm_topk_prob", False, "norm_topk_prob"),
             ("model_type", "kimi_linear", "")),
         bf16="slot"),
)}


def cases(of, id_is_a_value=False):
    """``pytest.param``s of one probe over ``SPECS``, a family's together:
    ``of(spec)`` yields ``(id, value..., marks)`` rows."""
    return [pytest.param(spec.name, *row[0 if id_is_a_value else 1:-1],
                         id=f"{spec.name}-{row[0]}", marks=row[-1])
            for spec in SPECS.values() for row in of(spec)]


def families(keep=lambda spec: True, marks=lambda spec: ()):
    return [pytest.param(spec.name, id=spec.name, marks=marks(spec))
            for spec in SPECS.values() if keep(spec)]
