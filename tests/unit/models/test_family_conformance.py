"""The probes every expert family answers, one case a family and variant
(``family_harness.SPECS``: a new family is a row there, not a file here): the
uncached ``apply`` and the slot path against the plain reference, every fault
of the configuration's control over the row's tolerance and the sound run
under it, the shares of a cut expert layer against the uncut layer, the
siblings the builder refuses by name, the file against ``BENCHMARK.json``'s
entry, what the family does not serve refused in its words, and bf16 under
the limits that decide ``correct``.  All in float32 at the rehearsal's tiny
sizes on the CPU, ``apply``, the slot path and the faults on ONE model a
family drawn at ``harness.LOUD`` (so that the layers and not the embedding
make the logits); what a family alone has is in its own file."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.chip.reference import compare
from deepspeed_tpu.models import cache_family, gpt_inference
from deepspeed_tpu.moe.held_experts import read_pair_counts
from tests.unit.chipbench.common import check_configuration
from tests.unit.models import family_harness as harness
from tests.unit.models.family_harness import ATOL, RTOL, SPECS


@pytest.mark.parametrize("name", harness.families(
    marks=lambda spec: spec.apply_marks))
def test_apply_equals_the_reference(name):
    spec = SPECS[name]
    np.testing.assert_allclose(
        harness.sound_applied(name), harness.sound_apply(name)[3],
        atol=ATOL, rtol=RTOL, err_msg=spec.why)


@pytest.mark.parametrize("name,lengths", harness.cases(
    lambda spec: spec.slot_paths))
def test_the_slot_path_equals_the_reference(name, lengths):
    """Chunked prefill (chunk boundaries crossed, tails padded), then 8
    decode ticks through the gateway's own programs and every kind of state
    the family caches, against the reference's full forward, on logits; and
    the counters the probe leaves, each group where the family's layout
    puts it."""
    spec = SPECS[name]
    cfg, params = harness.loud(spec)
    served = harness.loud_served(spec)
    assert cache_family(cfg) is served._batcher._fam \
        and isinstance(served._batcher._fam, gpt_inference.Family)
    before = harness.counters(served)
    pairs = harness.slot_path_logits(spec, served, params, lengths,
                                     **spec.keys)
    for n, (got, ref) in zip(lengths, pairs):
        np.testing.assert_allclose(got, ref, atol=ATOL, rtol=RTOL,
                                   err_msg=f"prompt of {n}: {spec.why}")
    harness.check_counters(spec, served, before, lengths)


@pytest.mark.parametrize("which", ["prefill", "extend"])
@pytest.mark.parametrize("name", harness.families())
def test_a_pass_asked_for_no_head_returns_what_the_head_takes(name, which):
    """``prefill`` / ``extend`` with ``head=False`` (what an admission's
    passes ask: the head runs once, after them, on one row) return the layer
    stack's output in the logits' place: the family's ``logits`` of it are
    the logits the pass returns by default, the cache is the same."""
    cfg, _ = harness.loud(SPECS[name])
    harness.check_a_pass_without_its_head(
        cfg, {"prefill": (1, 8), "extend": (1, 13)}[which],
        harness.loud_passes_both_ways(name)[which])


@pytest.mark.parametrize("name,fault,mode", harness.cases(
    lambda spec: spec.faults, id_is_a_value=True))
def test_a_planted_fault_reads_over_the_tolerance(name, fault, mode):
    """The faults of the configuration's control (``reference/*_control.py``:
    a function of the program replaced, or the server's weights changed), on
    weights drawn at ``LOUD``, through ``apply`` or through the slot path
    (the faults only a cache can show): the sound run reads under ``ATOL``
    and the fault over ``fault_over`` times it, all of one configuration
    against one forward of the reference; what was patched is put back.
    A row's ``decided`` faults also fail the comparison that decides
    ``correct``, which the sound run passes."""
    spec = SPECS[name]
    before = harness.patchable(spec)
    got, ref = harness.faulty_logits(spec, fault, mode)
    if mode == "slot":
        sound = harness.sound_slot(name)[2]
        assert harness.off(sound, ref) <= ATOL, spec.why
    if fault in spec.decided:
        assert compare.agrees(harness.sound_applied(name), ref) \
            and not compare.agrees(got, ref)
    if fault == "none":
        assert harness.off(got, ref) <= ATOL, spec.why
    elif fault in spec.faint:
        assert np.abs(got - sound).max() > 0
    else:
        assert harness.off(got, ref) > spec.fault_over * ATOL, \
            harness.off(got, ref)
    assert harness.patchable(spec) == before


@pytest.mark.parametrize("name", harness.families(lambda spec: spec.shares))
def test_the_shares_add_up_to_the_uncut_layer(name):
    """The routed parts every share of the deployment computes for its own
    experts, plus what every chip computes alike (the shared expert, the
    residual, the identity part) counted ONCE, are the layer that holds
    every expert, and that is the plain reference's uncut layer; every
    routed pair is some share's."""
    spec = SPECS[name]
    cut = spec.shares
    file = harness.tiny_file(name, **cut.keys)
    cfg, params = harness.model(spec, seed=4, std=harness.LOUD if cut.loud
                                else None, **cut.keys)
    if cut.uncut is not None:       # the tiny file holds a share: hold all
        cfg = cut.uncut(cfg)
        params = jax.jit(lambda key: spec.program.init(
            cfg, key, std=harness.LOUD))(jax.random.PRNGKey(7))
    assert len(cfg.held) == cfg.n_experts == cut.shares * cut.each
    p = cut.layer(params)
    down = cut.matrices[-1]
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 9, cfg.d_model))
    whole, counts = cut.ffn(x, p, cfg)
    counts = read_pair_counts(counts)
    routed, pairs = 0.0, 0
    for share in range(cut.shares):
        held = tuple(range(cut.each * share, cut.each * (share + 1)))
        part = dataclasses.replace(cfg, held_experts=held)
        mine = {**p, **{m: p[m][held[0]:held[-1] + 1] for m in cut.matrices}}
        out, c = cut.ffn(x, mine, part)
        alone, _ = cut.ffn(x, {**mine, down: mine[down] * 0}, part)
        routed = routed + (out - alone)         # this share's routed part
        c = read_pair_counts(c)
        pairs += c["held"]
        assert c["routed"] == counts["routed"] \
            == 2 * 9 * cfg.experts_per_token and c["zero"] == counts["zero"]
    assert pairs == counts["held"] == counts["routed"] - counts["zero"]
    assert (counts["zero"] > 0) == spec.zero_experts
    assert float(jnp.abs(routed).max()) > 0
    # ``alone``: what every chip computes alike, the same in every share
    np.testing.assert_allclose(np.asarray(alone + routed), np.asarray(whole),
                               atol=cut.atol, rtol=cut.rtol)
    if cut.reference is not None:
        np.testing.assert_allclose(
            np.asarray(whole), np.asarray(cut.reference(file, x, p)),
            atol=ATOL, rtol=RTOL)


@pytest.mark.parametrize("name,key,value,said", [
    pytest.param(spec.name, *row, id=f"{spec.name}-{row[0]}")
    for spec in SPECS.values() for row in spec.siblings])
def test_a_sibling_configuration_is_refused_by_name(name, key, value, said):
    spec = SPECS[name]
    file = harness.tiny_file(name) if spec.siblings_on_tiny \
        else harness.published(name)
    spec.family.build(file)                  # the file itself is served
    with pytest.raises(AssertionError, match=said or None):
        spec.family.build({**file, key: value})


@pytest.mark.parametrize("name", harness.families())
def test_the_file_is_the_entrys_but_for_what_reduced_lists(name):
    """The published file against its entry of ``BENCHMARK.json``
    (``check_configuration``: no width reduced, the builder's config
    reproduces the file), the published count of every reduced key beside
    the held one, and the tiny file builds the same family."""
    spec = SPECS[name]
    file, entry = harness.published(name), harness.entry(name)
    cfg = check_configuration(file, entry)
    assert set(entry["reduced"]) <= set(file["published"])
    for key in entry["reduced"]:
        assert file[key] < file["published"][key], key
    tiny, _ = harness.model(spec)
    assert cache_family(tiny) is cache_family(cfg)
    assert type(tiny) is type(cfg) and tiny.n_layer <= cfg.n_layer


FEATURES = ("speculative", "paging", "prefix")


@pytest.mark.parametrize("feature", FEATURES + ("int8",))
@pytest.mark.parametrize("name", harness.families())
def test_what_the_family_does_not_serve_is_refused_in_its_words(name,
                                                                feature):
    """``Family.unsupported`` names what a family does not serve and why:
    the batcher refuses it in those words (``serving.<feature> with
    <Config>: <why>``), the gateway at construction, a pooled prefix where
    it is built, and the int8 cache where the cache is made."""
    from deepspeed_tpu.serving.batcher import SlotBatcher
    spec = SPECS[name]
    cfg, params = harness.model(spec)
    fam = cache_family(cfg)
    assert {"int8", "draft"} <= set(fam.unsupported) \
        <= {*FEATURES, "int8", "draft"}
    if feature == "int8":
        with pytest.raises(NotImplementedError) as e:
            fam.init_cache(cfg, 1, 32, kv_dtype="int8")
        assert str(e.value) == fam.unsupported["int8"] \
            + " (kv_cache_dtype='int8')"
        return
    shell = object.__new__(SlotBatcher)
    shell._fam, shell._cfg = fam, cfg
    if feature not in fam.unsupported:      # what it serves is not refused
        assert shell.unsupported(feature) is None
        return shell.refuse(feature)
    why = fam.unsupported[feature]
    assert why == spec.said.get(feature, why) == shell.unsupported(feature)
    said = f"serving.{feature} with {type(cfg).__name__}: {why}"
    with pytest.raises(NotImplementedError) as e:
        shell.refuse(feature)
    assert str(e.value) == said
    base = {"slots": 2, "max_len": 64, "prefill_chunk": 16}
    asked = {"speculative": {"enabled": True, "draft_k": 2, "draft": {
        "n_layer": 1, "d_model": 32, "n_head": 2}},
        "paging": {"enabled": True, "block_size": 16, "hbm_blocks": 32}}
    if feature in asked:
        with pytest.raises(NotImplementedError) as e:
            harness.engine(cfg, params).serve(
                config={**base, feature: asked[feature]})
        assert str(e.value) == said
    else:
        served = harness.served(spec)
        toks = np.arange(20, dtype=np.int32) % cfg.vocab_size
        with pytest.raises(NotImplementedError) as e:
            served._batcher.build_prefix(toks[:8])
        assert str(e.value) == said
        with pytest.raises(NotImplementedError, match="prefix"):
            served.submit(toks, max_new_tokens=2, prefix_len=8)


@pytest.mark.parametrize("name", harness.families(lambda spec: spec.bf16))
def test_bf16_passes_the_cells_limits(name):
    """The program in bf16 against the float32 reference under
    ``compare.py``'s two limits (calibrated on the chip at the published
    widths), at the family's own scales: through ``apply``, or through the
    slot path (the timed path: three chunk edges, a padded tail, 8 ticks)
    where the row says so."""
    spec = SPECS[name]
    if spec.bf16 == "slot":
        _, params = harness.model(spec, dtype=jnp.bfloat16)
        pair, = harness.slot_path_logits(
            spec, harness.served(spec, dtype=jnp.bfloat16), params,
            harness.FAULT_PROMPT)
        assert compare.agrees(*pair)
        return
    cfg, params = harness.model(spec, seed=3)
    toks = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 48)).astype(np.int32)
    bf16 = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    got = jax.jit(lambda p, t: spec.program.apply(p, t, bf16))(
        params, toks)[:, -16:, :cfg.vocab_size]
    assert compare.agrees(got, harness.reference_logits(spec, params, toks,
                                                        16))


@pytest.mark.parametrize("name", harness.families(
    lambda spec: spec.readings))
def test_the_control_runs_the_check_through_the_slot_path(name):
    """The control's own ``readings`` (the script that sets the cell's
    limits on the chip) at the tiny size: a sound run is within the two
    limits that decide ``correct`` and each fault reads a larger rms; what
    was lent or patched is put back."""
    spec = SPECS[name]
    before = harness.patchable(spec)
    worst, rms = harness.control_readings(spec, "none")
    assert worst <= compare.TOLERANCE and rms <= compare.RMS_TOLERANCE
    for fault in spec.readings[1]:
        assert harness.control_readings(spec, fault)[1] > rms, fault
    assert harness.patchable(spec) == before
