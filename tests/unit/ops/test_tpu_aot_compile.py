"""The main path's Pallas kernels, compiled by the TPU's own compiler for a
described (not attached) v5e at the widths GPT-2 350M training and serving
use.  Interpret mode cannot see what Mosaic refuses (a cast it lacks, a
block that breaks the (8, 128) tiling, a kernel over its VMEM limit); these
compiles can, and cost no chip.  Nothing runs: a pass here is not a chip
run.
"""

import importlib
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs under /tmp

import pytest

import jax
import jax.numpy as jnp

_KERNEL_MODULES = [
    importlib.import_module(f"deepspeed_tpu.ops.pallas.{name}")
    for name in ("flash_attention", "decode_attention", "fused_bias_gelu",
                 "quantizer")]
flash, decode, gelu, quantizer = _KERNEL_MODULES
delta_rule = importlib.import_module("deepspeed_tpu.ops.pallas.delta_rule")
# the delta-rule kernels, the expert layer's grouped matmul and the
# state-space kernels ask the same two questions (the last two are taken by
# their place)
_KERNEL_MODULES += [delta_rule,
                    importlib.import_module("deepspeed_tpu.moe.held_experts"),
                    importlib.import_module("deepspeed_tpu.ops.pallas.ssm")]

BF16 = jnp.bfloat16
# GPT-2 350M: 16 heads of 64; training micro-batch rows x 1024 tokens,
# serving 4 slots over a 1024-token cache in 128-token prefill chunks
B, S, H, D = 8, 1024, 16, 64
SLOTS, SMAX, CHUNK = 4, 1024, 128
D_FF = 4096


@pytest.fixture(scope="module")
def v5e_host():
    """The four devices of a described v5e 2x2 host."""
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here: nothing to compile with
        pytest.skip(f"cannot describe a v5e topology: {e}")
    return topo.devices


@pytest.fixture(scope="module")
def v5e(v5e_host):
    """Sharding on one chip of that host."""
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(v5e_host[0])


@pytest.fixture(autouse=True)
def mosaic(monkeypatch):
    """Steer the kernel entries onto the real lowering (they ask
    ``jax.default_backend()``, which is the CPU here), with the persistent
    compile cache off: a described-device executable can be written to it
    but never read back."""
    from jax.experimental.compilation_cache import compilation_cache
    for mod in _KERNEL_MODULES:
        monkeypatch.setattr(mod, "use_pallas", lambda: True)
        monkeypatch.setattr(mod, "interpret_mode", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiles_with_kernel(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


def _flash_loss(q, k, v):
    return jnp.sum(flash.flash_attention(q, k, v, causal=True)
                   .astype(jnp.float32))


def _qkv(sh, b, s, h, d):
    return [jax.ShapeDtypeStruct((b, s, h, d), BF16, sharding=sh)] * 3


def test_flash_forward(v5e):
    _compiles_with_kernel(
        lambda q, k, v: flash.flash_attention(q, k, v, causal=True),
        *_qkv(v5e, B, S, H, D))


@pytest.mark.parametrize("heads,head_dim", [(H, D), (8, 128)])
def test_flash_forward_backward(v5e, heads, head_dim):
    _compiles_with_kernel(jax.grad(_flash_loss, argnums=(0, 1, 2)),
                          *_qkv(v5e, B, S, heads, head_dim))


# The train cells' per-chip attention (PERF.md 4): gpt2m-train-s1024 is 24
# rows x 16 heads of one 1024-block, opt1b3-train-zero3-4chip 8 rows x 32
# heads of 2 x 2.  MiB of scoped VMEM the kernels plan (forward, gradient;
# found in 2 MiB steps; the compiler's own report, PR 43).  At one block a
# head the causal strips plan what PR 37's parent planned, a head pair's
# wider tiles included; at 2 x 2 the whole-block body of a pair's two
# unrolled heads sets the plan (15.7 and 21.6 MiB; one head a grid step
# planned 10 and 12) and the kernels ask for more than the 16 MiB default
# (``_compiler_params``).
_TRAIN_CELLS = {"gpt2m-train-s1024": ((24, 1024, 16, 64), (8, 10)),
                "opt1b3-train-zero3-4chip": ((8, 2048, 32, 64), (16, 22))}


def _packed_loss(heads):
    return lambda qkv: jnp.sum(flash.flash_attention_packed(
        qkv, heads, causal=True).astype(jnp.float32))


@pytest.mark.parametrize("form", ["packed", "three"])
@pytest.mark.parametrize("grad", [False, True], ids=["forward", "grad"])
@pytest.mark.parametrize("cell", list(_TRAIN_CELLS))
def test_flash_at_the_train_cells_shapes(v5e, monkeypatch, cell, grad, form):
    """The custom calls keep the names by which the benchmark finds them
    and return token-major rows, inside the VMEM plan above, for the packed
    product and for three arrays.  What ``flops.flash_call`` counts for the
    new result shapes today (``readers/flash_roofline.py``): a backward's
    three ``bf16[B,S,H*D]`` read as ``(BH, S, D) = (B, S, H*D)``, the same
    product, so its ``10 B H S^2 D / 2`` operations exactly; the forward
    NOTHING, because its ``lse`` is ``f32[B,H,1,S]`` and no longer the
    ``f32[BH,1,S]`` that marks a forward (its time still counts, so
    ``kernels.flash_roofline.train`` reads low, never over: PERF.md 7; the
    next ``benchmark`` issue should count flash work from the engine's
    counters, not from shapes)."""
    import re
    from benchmarks.chip.flops import flash_call
    (b, s, h, d), mib = _TRAIN_CELLS[cell]
    params = flash.pltpu.CompilerParams
    monkeypatch.setattr(flash.pltpu, "CompilerParams", lambda **kw: params(
        **{**kw, "vmem_limit_bytes": mib[grad] << 20}))
    if form == "packed":
        loss = _packed_loss(h)
        fn = jax.grad(loss) if grad else (
            lambda qkv: flash.flash_attention_packed(qkv, h, causal=True))
        shapes = [jax.ShapeDtypeStruct((b, s, 3 * h * d), BF16, sharding=v5e)]
    else:
        fn = jax.grad(_flash_loss, argnums=(0, 1, 2)) if grad else (
            lambda q, k, v: flash.flash_attention(q, k, v, causal=True))
        shapes = _qkv(v5e, b, s, h, d)
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    calls = {m[1]: m[2] for m in re.finditer(
        r"%(\S*flash_\w+?)[_.\d]* = (.*?) custom-call\(.*tpu_custom_call", text)}
    rows = f"bf16[{b},{s},{h * d}]"
    fwd = f"({rows}, f32[{b},{h},1,{s}])"
    bwd = f"({rows}, {rows}, {rows})"
    strip = lambda shape: re.sub(r"\{[^}]*\}", "", shape)
    assert strip(calls.pop(next(n for n in calls if n.endswith("flash_fwd")))
                 ) == fwd
    assert flash_call(fwd) == (0.0, 0.0)
    if grad:
        assert strip(calls.pop(next(
            n for n in calls if n.endswith("flash_bwd")))) == bwd
        assert flash_call(bwd) == (10.0 * b * h * s * s * d / 2,
                                   7 * b * h * s * d * 2 + 8 * b * s)
    assert not calls
    # no float32 partial of dq and nothing re-laid: the packed product goes
    # in as it is, three times, and rows come out
    assert not re.search(rf"f32\[{b * h},\d+,{s},{d}\]", text)
    if form == "packed":
        assert not re.search(r" (copy|transpose)\(", text), text


def test_flash_forward_backward_on_dp2_tp2_mesh(v5e_host):
    """The compiler cannot partition a Mosaic kernel; under a mesh the
    kernel entry maps it over rows and heads itself."""
    from deepspeed_tpu.parallel.mesh import (DP_GROUP, MODEL_AXIS,
                                             ParallelDims, initialize_mesh)
    mm = initialize_mesh(ParallelDims(dp=2, tp=2), devices=v5e_host)
    sh = mm.sharding(DP_GROUP, None, MODEL_AXIS, None)
    _compiles_with_kernel(jax.grad(_flash_loss, argnums=(0, 1, 2)),
                          *_qkv(sh, B, S, H, D))


def _cache(sh, int8):
    shape = (SLOTS, SMAX, H, D)
    if not int8:
        kv = jax.ShapeDtypeStruct(shape, BF16, sharding=sh)
        return kv, kv, None, None
    codes = jax.ShapeDtypeStruct(shape, jnp.int8, sharding=sh)
    scale = jax.ShapeDtypeStruct(shape[:3] + (1,), jnp.float32, sharding=sh)
    return codes, codes, scale, scale


@pytest.mark.parametrize("sq,masked", [(1, False), (1, True), (CHUNK, False)],
                         ids=["decode", "decode-masked", "chunk"])
@pytest.mark.parametrize("int8,per_row_pos",
                         [(False, True), (True, False), (True, True)],
                         ids=["bf16-rowpos", "int8-scalarpos", "int8-rowpos"])
def test_cached_attention(v5e, sq, masked, int8, per_row_pos):
    """``masked``: the tick's ``active`` mask rides in; the decode sweep's
    grid bound is then (as always on the chip) the live-block count, a
    dynamic bound that only this lowering accepts."""
    q = jax.ShapeDtypeStruct((SLOTS, sq, H, D), BF16, sharding=v5e)
    pos = jax.ShapeDtypeStruct((SLOTS,) if per_row_pos else (), jnp.int32,
                               sharding=v5e)
    active = jax.ShapeDtypeStruct((SLOTS,), jnp.bool_, sharding=v5e) \
        if masked else None
    k, v, ks, vs = _cache(v5e, int8)
    _compiles_with_kernel(
        lambda q, k, v, pos, ks, vs, active: decode.cached_attention(
            q, k, v, pos, k_scale=ks, v_scale=vs, active=active),
        q, k, v, pos, ks, vs, active)


def _root_opcodes(hlo_text):
    """``(result elements, opcode)`` of every instruction of a compiled
    module, a fusion counted under the opcode of its root."""
    import math
    import re
    line = re.compile(r"^\s*(ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(")
    roots, rows, name = {}, [], None
    for text in hlo_text.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) \(.*\{$", text)
        if head:
            name = head.group(1)
            continue
        m = line.match(text)
        if not m:
            continue
        root, _, dims, opcode = m.groups()
        if root:
            roots[name] = opcode
        calls = re.search(r"calls=%(\S+?)[,)\s]", text)
        n = math.prod(int(d) for d in dims.split(",") if d)
        rows.append((n, opcode, calls.group(1) if calls else None))
    return [(n, roots.get(calls, op) if op == "fusion" else op)
            for n, op, calls in rows]


def _sweep_is_built_outside_the_layer_scan(jaxpr, slots, segments=1,
                                           kernel_free=0):
    """The kernel's work list is a function of the tick's inputs alone: its
    running sum (``decode_sweep``'s ``cumsum`` over the rows' block counts,
    rank 1, one entry a slot; an MoE gate's running sums over ``[tokens,
    experts]`` and a grouped matmul's over its groups and tiles are not it)
    is an equation of the tick and of no layer's body, where the kernel
    call itself sits.  ``kernel_free``: the segments whose layers call no
    kernel at all (a run of convolution mixers over dense FFNs)."""
    def names(jp):
        return [e.primitive.name if e.primitive.name != "cumsum"
                or e.invars[0].aval.shape != (slots,) else "sweep_cumsum"
                for e in jp.eqns]

    def inner(eqn):
        return [getattr(v, "jaxpr", v) for v in eqn.params.values()
                if hasattr(getattr(v, "jaxpr", v), "eqns")]

    def groups_as_many_as_slots(eqn):
        # a grouped matmul handed as many groups as there are slots (reading
        # its layer of a stack in place it has ``layers x held experts``: 4
        # x 16 = 64 in the shortcut family's cell): the running sums over
        # its own ``group_sizes`` are not the sweep's.  Any other count and
        # every slots-long cumsum under it still counts.
        return eqn.primitive.name in ("jit", "pjit") \
            and eqn.params.get("name") == "gmm" and any(
                v.aval.shape == (slots,) and v.aval.dtype == jnp.int32
                for v in eqn.invars)

    def deep(jp):
        return names(jp) + [n for e in jp.eqns
                            if not groups_as_many_as_slots(e)
                            for sub in inner(e) for n in deep(sub)]

    # one scan a segment of the family's step (a stack with leading dense
    # layers has two)
    scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == segments, names(jaxpr)
    around = [n for e in jaxpr.eqns if e not in scans
              for sub in inner(e) for n in deep(sub)]
    assert "sweep_cumsum" in around, around
    bodies = [deep(inner(scan)[0]) for scan in scans]
    assert sum("pallas_call" not in body for body in bodies) == kernel_free
    for body in bodies:
        assert "sweep_cumsum" not in body, \
            "the sweep is rebuilt in every layer"


_SERVED = pytest.mark.parametrize("family,int8", [
    ("dense", False), ("dense", True), ("moe", False), ("moe", True),
    ("latent", False), ("hybrid", False), ("single_part", False),
    ("window", False), ("linear", False), ("shortcut", False),
    ("conv", False)],
    ids=["bf16-dense", "int8-dense", "bf16-moe", "int8-moe", "bf16-latent",
         "bf16-hybrid", "bf16-single_part", "bf16-window", "bf16-linear",
         "bf16-shortcut", "bf16-conv"])

def _cell_files(cell):
    """``(configuration file, traffic file)`` of one ``workloads`` entry of
    ``BENCHMARK.json``: what the cell runs."""
    import json
    root = os.path.join(os.path.dirname(__file__), "..", "..", "..")

    def read(*parts):
        with open(os.path.join(root, *parts)) as f:
            return json.load(f)
    entry = next(w for w in read("BENCHMARK.json")["workloads"]
                 if w["name"] == cell)
    return (read("benchmarks", "chip", "configs", entry["config"] + ".json"),
            read("benchmarks", "chip", "traffic", entry["traffic"] + ".json"))


#: family -> (cell, cut).  The model, its configuration and its
#: weights' shapes come from the cell's own configuration file through its
#: ``builder`` and ``init`` hooks (as ``kinds/_serving.py`` builds them), the
#: slots, slot length and chunk from its traffic file's ``serving`` block:
#: nothing of a cell is written here, so a file that changes changes what the
#: guards compile (``test_the_guards_compile_the_cells_own_files``).  ``cut``
#: is what every guard compiles shallower or narrower than the cell, said in
#: the row; a row without one compiles the cell's own stack.
_GUARDED = {
    # two layers stand for 24 (the ladder's plan is read at 24:
    # ``_LADDER_LAYERS``)
    "dense": ("gpt2m-serve-decode-sat", dict(n_layer=2)),
    # no cell of its own: GPT-MoE at ``gpt2-medium``'s widths, one (dense,
    # expert) pair of four experts
    "moe": ("gpt2m-serve-decode-sat", dict(n_layer=2)),
    # one dense and two expert layers of the cell's five, 4 of its 12 held
    # experts, under a tenth of its vocabulary: a layer of the pool is then
    # larger than any one matrix, so "as large as a layer" still means the
    # pool (1,920 and not 2,048, an expert's inner width: the guard of the
    # head, ``the_head_once_on_one_row``, tells the head's arrays by the
    # vocabulary among their dimensions)
    "latent": ("kimik2-serve-reason-sat", dict(
        n_layer=3, held_experts=(0, 1, 2, 3), vocab_size=1920)),
    # the whole period of ten layers, ``M x 5 * M x 4`` as three scans: 4.9
    # GB of float32 state a slot pool keeps beside 2.7 GB of grouped KV,
    # which the compiler must not copy even once
    "hybrid": ("granite4h-serve-rag-sat", {}),
    # ``(ME)x2 M * (EM)x3 * (EM)x2 E``: all 18 layers as seven scans
    "single_part": ("nemotron3n-serve-agent-sat", {}),
    # all 28 layers, ``(WWWF) x 7`` as ONE scan: depth costs the compiler
    # nothing, and 7 layers of one slot's whole rows against one ring layer
    # of 48 slots is what ``_KNOWN_MOVES`` is sized by
    "window": ("mellum2-serve-code-sat", {}),
    # ``K+dense, K x 2, L, K x 3, L``: all 8 layers as five scans
    "linear": ("kimilin-serve-think-sat", {}),
    "shortcut": ("longcat-serve-docqa-sat", {}),
    "selected": ("dots3n-serve-longgen-sat", {}),
    # all 14 layers, ``(C+dense) x 2, (A C C C) x 3`` as two scans, every one
    # of a layer's 32 experts: 9.3 GB of weights beside a 4.8 GB pool of
    # grouped KV at a head of 64 (this is where a 64-lane concatenation or
    # store Mosaic refused would show before any chip)
    "conv": ("lfm2-serve-assist-sat", {}),
}


def _served(family, **deeper):
    """``(init, config, slots, slot length, chunk)`` of a row of
    :data:`_GUARDED`: ``init(key)`` the weights in the type they are served
    in, as the cell's kind draws them; ``deeper``: fields put back over the
    row's cut."""
    import dataclasses

    from benchmarks.chip.builders import resolve
    cell, cut = _GUARDED[family]
    file, traffic = _cell_files(cell)
    cfg = resolve(file["builder"])(file)
    draw = resolve(file["init"])
    if family == "moe":
        from deepspeed_tpu.models import gpt_moe
        cfg = gpt_moe.GPTMoEConfig(
            **{f.name: getattr(cfg, f.name)
               for f in dataclasses.fields(cfg)}, num_experts=4)
        draw = lambda cfg, key, dtype: jax.tree_util.tree_map(
            lambda w: w.astype(dtype), gpt_moe.init(cfg, key))
    cfg = dataclasses.replace(cfg, **{**cut, **deeper})
    serving = traffic["serving"]
    return (lambda key: draw(cfg, key, BF16)), cfg, serving["slots"], \
        serving["max_len"], serving["prefill_chunk"]


@pytest.mark.parametrize("family", sorted(_GUARDED))
def test_the_guards_compile_the_cells_own_files(family):
    """A row of :data:`_GUARDED` departs from its cell's files in what its
    ``cut`` names and in nothing else: every other field of
    the configuration is the cell's builder's, the geometry is the traffic
    file's, and the weights are its ``init``'s in bf16."""
    import dataclasses

    from benchmarks.chip.builders import resolve
    cell, cut = _GUARDED[family]
    file, traffic = _cell_files(cell)
    init, cfg, slots, smax, chunk = _served(family)
    own = resolve(file["builder"])(file)
    named = set(cut) | ({"num_experts"} if family == "moe" else set())
    differs = {f.name for f in dataclasses.fields(own)
               if getattr(own, f.name) != getattr(cfg, f.name)}
    assert differs <= named, differs - named
    assert bool(differs) == bool(named)     # and a named cut does cut
    assert (slots, smax, chunk) == tuple(
        traffic["serving"][k] for k in ("slots", "max_len", "prefill_chunk"))
    leaves = jax.tree_util.tree_leaves(
        jax.eval_shape(init, jax.random.PRNGKey(0)))
    assert leaves and all(
        x.dtype in (BF16, jnp.float32, jnp.int32) for x in leaves)
    assert any(x.dtype == BF16 for x in leaves)
    # a cut is shallower or narrower, never wider
    for key, value in cut.items():
        was = getattr(own, key)
        size = lambda v: len(v) if isinstance(v, tuple) else v
        assert size(value) <= size(was), key


def _segments(fam, cfg, params):
    """Scans of a tick: one a segment of the family's step."""
    found = []
    jax.eval_shape(lambda p: found.append(len(fam.step(
        p, cfg, jnp.ones((1,), jnp.int32)))), params)
    return found[0]


def _described(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _pool_bytes(cache):
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(cache) if x.ndim >= 4)


#: The window-and-full family's smallest stack is a RING's layer, 48 slots
#: of 1,024 cells x 512 (25.2M elements), under which two arrays that are
#: not the pool fall over the line of "as large as a layer of the pool".
#: Each is named here with its size, opcode, count and cause; anything else
#: that large, a weight stack re-laid or one move more of these, fails.
#: Every other family moves nothing that large and is held to nothing.
_KNOWN_MOVES = {
    # the untied head ``[24576, 2304]`` (56.6M elements), fetched ahead into
    # the compiler's fast memory space by cross-program prefetch
    # (``copy-start(%p__head__), cross_program_prefetch_index=0``): the same
    # tiled layout on both sides, no re-lay, and the one read a tick makes
    # of the head anyway
    ("window", "tick"): {(24576 * 2304, "copy-done"): 1},
    # the admission's BATCH-1 row, its two full banks ``[7, 1, 8192, 512]``
    # (29.4M elements each: 7 layers of one slot are more than one ring
    # layer of 48): once an admission, after the chunk loop, each bank is
    # gathered from the loop's carry (``ConcatBitcast`` + ``copy``) and
    # fetched (``copy-done``) for the slot write.  0.24 GB moved beside the
    # 0.3 s of a 4k-token admission (PERF.md 5); the accepted families' rows
    # make the same trip under their thresholds
    ("window", "admission"): {(7 * 8192 * 512, "copy"): 2,
                              (7 * 8192 * 512, "copy-done"): 2},
}


def _beyond_the_known(moved, family, program):
    """``moved`` less what :data:`_KNOWN_MOVES` names for this family's
    program, each at most as often as it is named."""
    import collections
    return sorted((collections.Counter(moved) - collections.Counter(
        _KNOWN_MOVES.get((family, program), {}))).elements())


def _layer_elements(cfg, cache, slots, smax):
    """Elements of one layer of the pool's smallest large stack: a bank's,
    or the per-slot state's or the ring's where the family keeps one.  A
    stack that is IN ALL under a tenth of one layer of a bank is no stack of
    the pool's size and sets no threshold (a convolution's tails, two rows a
    slot a layer: 11 layers are 23 MB where a layer of a bank is 805 MB, and
    a line drawn at one layer of them, 2 MB, would take every activation of
    a tick for the pool); every other stack does, and
    :data:`_LAYER_ELEMENTS` pins what each family is held to."""
    from deepspeed_tpu.models.gpt_inference import cache_row
    layers = [slots * smax * cache_row(cfg)[0]]
    for stacks in (cache.state, cache.ring):
        if stacks is not None and 10 * stacks[0].size >= layers[0]:
            layers.append(stacks[0].size // stacks[0].shape[0])
    return min(layers)


#: What "as large as a layer of the pool" is for each row of
#: :data:`_GUARDED`, in elements, at its cell's geometry: the line over which
#: a tick or an admission may copy, transpose or slice nothing but what
#: :data:`_KNOWN_MOVES` names.  Written out so that a change to
#: :func:`_layer_elements` that loosens a family's guard shows as a change
#: here: a layer of a bank where that is the pool's only stack, else a layer
#: of the state (hybrid, single-part, linear: 128 or 256 slots of a
#: recurrent state) or of the ring (window, selected).
_LAYER_ELEMENTS = {
    "dense": 256 * 1024 * 256, "moe": 256 * 1024 * 256,
    "latent": 128 * 8192 * 640, "shortcut": 64 * 6144 * 640,
    "hybrid": 128 * 128 * 8192, "single_part": 128 * 128 * 4096,
    "linear": 256 * 128 * 4096,
    "window": 48 * 1024 * 512, "selected": 80 * 640 * 1152,
    # a layer of a bank: 256 slots x 3,072 tokens x 8 heads of 64
    "conv": 256 * 3072 * 512,
}


@pytest.mark.parametrize("family", sorted(_GUARDED))
def test_a_layer_of_the_pool_is_what_it_was(family):
    from deepspeed_tpu.models import cache_family
    _, cfg, slots, smax, _ = _served(family)
    cache = jax.eval_shape(
        lambda: cache_family(cfg).init_cache(cfg, slots, smax))
    assert _layer_elements(cfg, cache, slots, smax) == \
        _LAYER_ELEMENTS[family]


def _kernel_free_runs(cfg):
    """Runs of the family's step whose layers call no kernel at all: a
    convolution mixer (``causal_conv`` is XLA's) over a dense FFN, read off
    the config's own labels."""
    from deepspeed_tpu.models.conv_moe import CONV
    from deepspeed_tpu.models.hybrid_ssm_moe import DENSE
    return sum(all(label == CONV + DENSE for label in unit)
               for unit, _, _ in getattr(cfg, "units", ()))


def _pair_rows(text, cfg, params, tokens, matrices_that_tall=False):
    """Arrays of the compiled module with a row for every (token, choice)
    pair of a call of ``tokens`` tokens, ``[T * k, w]`` or ``[T, k, w]``, at
    a width ``w`` of the routed experts' matrices (the model's, an
    expert's, gate beside up): the held experts' path moves the pairs held
    HERE, a page of ``held_experts.pairs_cap`` rows, and what it keeps a
    pair of the whole call is integers (``held_experts_ffn``).  Empty for a
    family with no held experts, and for one that holds every expert: every
    pair is then held here, and its row is the layer's to move."""
    import re
    k = getattr(cfg, "experts_per_token", None)
    if not getattr(cfg, "held", None) or len(cfg.held) == cfg.n_experts:
        return []
    widths = set()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        if any(getattr(key, "key", None) in ("w_gu", "w_up", "w_down")
               for key in path):
            widths |= set(leaf.shape[-2:]) | {leaf.shape[-1] // 2}
    assert cfg.d_model in widths
    rows = f"{tokens * k}|{tokens},{k}"
    found = set(re.findall(
        rf"\b\w+\[(?:{rows}),(?:{'|'.join(map(str, sorted(widths)))})\]",
        text))
    if not matrices_that_tall:
        return sorted(found)
    # where the call's pairs are as many as a matrix of the model's is tall
    # (:data:`_PAIRS_AS_MANY_AS_A_MATRIX_IS_TALL`), that matrix is no row of
    # pairs
    matrices = {f"[{leaf.shape[-2]},{leaf.shape[-1]}]"
                for leaf in jax.tree_util.tree_leaves(params)
                if leaf.ndim >= 2}
    assert any(m.startswith(f"[{tokens * k},") for m in matrices)
    return sorted(m for m in found if m[m.index("["):] not in matrices)


#: the admissions whose chunk routes as many pairs as the model's matrices
#: are tall, so that :func:`_pair_rows` cannot tell a weight from a row of
#: pairs by its shape: 512 tokens x 12 choices = 6,144 = ``d_model``.  No
#: other family's call collides, and each keeps the plain predicate.
_PAIRS_AS_MANY_AS_A_MATRIX_IS_TALL = {"shortcut"}


@_SERVED
def test_decode_tick_leaves_the_pool_in_place(v5e, family, int8):
    """The tick's device program at the serving cells' geometry
    (:func:`_served`), for every model family: per-row ``decode_step`` on a
    donated cache, told which slots are live (the latent row stored as 576
    lanes: the compiler copies the whole pool around the kernel, PERF.md
    6).  The kernel's work list is built once, outside the layer
    scan.  Nothing but the kernel may touch a
    whole layer of the pool: no copy, transpose or slice as large as one
    layer's K, and the pool's inputs are its outputs.  A pool stored with
    64 last (``[L, B, S, H, D]``) fails this: the TPU lays it out with the
    tokens on the lanes and re-lays it around every write and kernel call."""
    from deepspeed_tpu.models import cache_family
    init, cfg, slots, smax, _ = _served(family)
    fam = cache_family(cfg)

    params = _described(jax.eval_shape(init, jax.random.PRNGKey(0)), v5e)
    cache = _described(jax.eval_shape(lambda: fam.init_cache(
        cfg, slots, smax, kv_dtype="int8" if int8 else None)), v5e)
    rows = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=v5e)
    live = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=v5e)
    tick = jax.jit(
        lambda p, c, tok, lengths, active: fam.decode_step(
            p, tok, cfg, c, lengths=lengths, active=active),
        donate_argnums=(1,))
    traced = tick.trace(params, cache, rows, rows, live).jaxpr
    _sweep_is_built_outside_the_layer_scan(
        traced, slots, segments=_segments(fam, cfg, params),
        kernel_free=_kernel_free_runs(cfg))
    _sweeps_in_blocks_of(traced, cfg, slots, smax, _SWEEP_BLOCK[family])
    compiled = tick.lower(params, cache, rows, rows, live).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the tick"
    layer_k = _layer_elements(cfg, cache, slots, smax)
    moved = [(n, op) for n, op in _root_opcodes(text)
             if n >= layer_k and (op.startswith("copy") or op in (
                 "transpose", "dynamic-slice", "dynamic-update-slice"))]
    moved = _beyond_the_known(moved, family, "tick")
    assert not moved, f"the tick moves whole layers of the pool: {moved}"
    assert not _pair_rows(text, cfg, params, slots), \
        "the tick builds rows for the pairs held elsewhere"
    assert "input_output_alias" in text
    assert compiled.memory_analysis().alias_size_in_bytes >= \
        _pool_bytes(cache), "the donated pool is not updated in place"
    if family in ("dense", "moe"):
        _one_dense_sweep_a_layer(text, cfg, cache, slots, smax,
                                 calls=1 if family == "dense" else 2)
    if family in _LATENT_TICKS:
        # a chunk's form is not the tick's affair: one query a row keeps
        # the absorbed sweep, its custom calls and its plan the ones the
        # program had before a chunk could up-project (PR 58)
        calls, planned = _LATENT_TICKS[family]
        assert sorted(_custom_calls(text)) == sorted(calls)
        assert _planned_bytes(compiled) <= planned


def _custom_calls(hlo_text):
    """``(kernel, result)`` of every Mosaic custom call of a compiled
    module, the kernel by the name it was launched under."""
    import re
    return [(name.split(".")[0], shape) for name, shape in re.findall(
        r"%([\w.]+) = (\w+\[[\d,]*\])\S* custom-call\([^\n]*"
        r"custom_call_target=\"tpu_custom_call\"", hlo_text)]


#: the tick of the three families whose chunks may up-project, as it
#: compiled BEFORE they could (compiler, PR 58, the parent's programs at
#: these geometries): the absorbed sweep once a sublayer body, the held
#: experts' two grouped products, and the bytes the program plans
_LATENT_TICKS = {
    "latent": ([("gmm", "bf16[128,4096]"), ("gmm", "bf16[128,7168]")]
               + [("latent_decode_attention", "bf16[128,1,32768]")] * 2,
               6430162944),
    "linear": ([("gmm", "bf16[512,2048]"), ("gmm", "bf16[512,2304]")] * 4
               + [("latent_decode_attention", "bf16[256,1,16384]")] * 2,
               13081361920),
    "shortcut": ([("gmm", "bf16[128,4096]"), ("gmm", "bf16[128,6144]")]
                 + [("latent_decode_attention", "bf16[64,1,32768]")] * 2,
                 14382130176),
}


#: tokens in a block of a family's single-token sweep at its cell's
#: geometry: 256 for the 1,024-wide rows (both ``gpt2-medium`` cells and
#: Granite's 8 key-value heads of 128), 1,024 for Nemotron's 256-wide row
#: (PR 46), the latent sweep's own 512
_SWEEP_BLOCK = {"dense": 256, "moe": 256, "hybrid": 256,
                "single_part": 1024, "latent": 512, "window": 512,
                "linear": 512, "shortcut": 512, "conv": 512}


def _sweeps_in_blocks_of(jaxpr, cfg, slots, smax, block):
    """Every single-token sweep of the traced tick is built for ``block``
    tokens a step, and so is the work list it walks; a ring's list is its
    own pool's, ``slots * R / block`` entries (``sweep_calls`` reads a
    list's length as if it covered ``smax``)."""
    from deepspeed_tpu.models.gpt_inference import cache_ring, cache_row
    from tests.unit.ops.traced_sweeps import sweep_calls
    calls = sweep_calls(jaxpr, slots, smax, cache_row(cfg)[0])
    ring = cache_ring(cfg, smax)
    lists = {(block, block)} | (
        {(block, block * smax // ring[1])} if ring else set())
    assert calls and {c[1:] for c in calls} == lists, calls


def _one_dense_sweep_a_layer(text, cfg, cache, slots, smax, calls):
    """The dense sweep copies its own blocks (PR 45): the pool's banks go
    in whole, in HBM, and the kernel holds three buffers of one block a
    bank.  It is still ONE custom call a layer of the scan's body (the MoE
    family's body is a dense and an expert layer), named
    ``decode_attention`` with the result ``bf16[B, 1, H*D]`` by which the
    benchmark's roofline reader finds it, and its scoped VMEM is the six
    buffers, 3 MiB of bf16 (1.5 MiB of codes), beside the 64 KiB
    accumulator and an int8 cache's four pipelined scale blocks of 128
    KiB: a quarter of the 16 MiB a v5e's kernel may use."""
    import re
    HD = cfg.n_head * cfg.head_dim
    found = re.findall(
        r"%(decode_attention[.\d]*) = (\w+\[[\d,]*\])\S* custom-call\(", text)
    assert [shape for _, shape in found] == [f"bf16[{slots},1,{HD}]"] * calls, \
        found
    block_k = decode.decode_block_k(smax, HD)
    buffers = 2 * 3 * block_k * HD * cache.k.dtype.itemsize
    scales = 0 if cache.k_scale is None else 2 * 2 * block_k * 128 * 4
    assert buffers == (3 << 20) // (2 // cache.k.dtype.itemsize)
    assert buffers + scales + cfg.n_head * HD * 4 < (16 << 20) // 4


def _planned_bytes(compiled):
    """What the program holds at once: arguments, results that alias none
    of them, temporaries."""
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def _pool_sized_moves(hlo_text, layer_k):
    """Copies, transposes and slices whose result is as large as a layer of
    the pool, and dynamic-update-slices whose UPDATE is (their result is
    the buffer written into: the pool itself, in place)."""
    import math
    import re
    shape = r"\w+\[([\d,]*)\]\S*"
    dus = re.compile(rf"dynamic-update-slice\({shape} %\S+, {shape} %")
    moved = [(n, op) for n, op in _root_opcodes(hlo_text)
             if n >= layer_k and (op.startswith("copy")
                                  or op in ("transpose", "dynamic-slice"))]
    for m in dus.finditer(hlo_text):
        n = math.prod(int(d) for d in m.group(2).split(",") if d)
        if n >= layer_k:
            moved.append((n, "dynamic-update-slice"))
    return moved


def _compile_admission(v5e, family, int8, layers=None):
    """``serving.batcher.admission`` of a family, and its widest pass's
    ``extend`` on its batch-1 row alone (GPT-2's, at chunks of 128 in slots
    of 1,024, is the ladder's 256 tokens; every other family's its chunk,
    from which its ladder goes down),
    compiled for the described chip at the serving cells' geometry
    (:func:`_served`; ``layers`` deep where given): ``(admit, extend, pool,
    row cache)``, the last two as shapes."""
    from deepspeed_tpu.models import cache_family
    from deepspeed_tpu.serving.batcher import admission, pass_widths
    init, cfg, slots, smax, chunk = _served(
        family, **({"n_layer": layers} if layers else {}))
    fam = cache_family(cfg)
    kv = "int8" if int8 else None
    # the weights in the type they are served in (a cast of the master
    # weights' is loop-invariant, and hoisted out of the chunk loop it
    # would count as the program's)
    params = _described(jax.eval_shape(init, jax.random.PRNGKey(0)), v5e)
    pool = _described(jax.eval_shape(
        lambda: fam.init_cache(cfg, slots, smax, kv_dtype=kv)), v5e)
    row_cache = _described(jax.eval_shape(
        lambda: fam.init_cache(cfg, 1, smax, kv_dtype=kv)), v5e)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    tokens = arg((1, pass_widths(chunk, smax)[0]), jnp.int32)
    extend = jax.jit(
        lambda p, t, c, l: fam.extend(p, t, cfg, c, lengths=l)).lower(
            params, tokens, row_cache, arg((1,), jnp.int32)).compile()
    vocab = jax.eval_shape(
        lambda p, t, c: fam.extend(p, t, cfg, c)[0], params, tokens,
        row_cache).shape[-1]
    per_slot = [arg((slots,) + tail, dtype) for tail, dtype in (
        ((), jnp.int32), ((vocab,), jnp.float32), ((2,), jnp.uint32),
        ((), jnp.bool_), ((), jnp.float32), ((), jnp.bool_))]
    compiled = jax.jit(
        admission(fam, cfg, smax, kv), donate_argnums=(1, 3)).lower(
            params, pool, *per_slot, arg((smax // chunk, chunk), jnp.int32),
            arg((7,), jnp.int32), arg((2,), jnp.uint32)).compile()
    return compiled, extend, pool, row_cache


@pytest.fixture(scope="module")
def admission_of(v5e):
    """:func:`_compile_admission` of ``(family, int8, layers)``, once each a
    process: the guards below read one compile where a worker runs them."""
    compiled = {}

    def of(*asked):
        if asked not in compiled:
            compiled[asked] = _compile_admission(v5e, *asked)
        return compiled[asked]

    return of


#: the depth of the cell whose admission holds the ladder's wide loop
#: (``gpt2-medium``'s 24 layers, where :func:`_served` lets two stand for
#: them): its plan guards are read there.  While a row's banks are a few MB
#: the compiler fetches them whole into its fast memory for the 256-row
#: body, and the plan reads 10-17 MB more than the depth-free parts it is
#: held to (PERF.md 6, PR 52, gives both depths' sizes).
_LADDER_LAYERS = 24


def _admission_is_one_program_on_the_pool_in_place(admission_of, family,
                                                   int8):
    """The admission's device program (``serving.batcher.admission``: the
    chunk loop, the slot write and the bind) at the serving cells' geometry
    (:func:`_served`), for every model family.  A program that holds a
    ``fori_loop`` beside the donated pool must still write the pool where it
    lies: the pool's inputs are its outputs, nothing copies, slices or
    updates as much as a layer of it (the slot write's update is one row of
    every layer), and the plan is what its widest pass's ``extend`` on the
    batch-1 row and the pool hold between them today.  A family with held
    experts moves the pairs held here and no row for the others."""
    init, cfg, slots, smax, chunk = _served(family)
    compiled, extend, pool, _ = admission_of(family, int8, None)
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the admission"
    assert " while(" in text, "no loop over the chunks"
    assert not _pair_rows(text, cfg, jax.eval_shape(
        init, jax.random.PRNGKey(0)), chunk,
        family in _PAIRS_AS_MANY_AS_A_MATRIX_IS_TALL), \
        "a chunk builds rows for the pairs held elsewhere"
    moved = _beyond_the_known(
        _pool_sized_moves(text, _layer_elements(cfg, pool, slots, smax)),
        family, "admission")
    assert not moved, f"the admission moves whole layers of the pool: {moved}"
    assert "input_output_alias" in text
    assert compiled.memory_analysis().alias_size_in_bytes >= \
        _pool_bytes(pool), "the donated pool is not updated in place"
    if family in _LATENT_TICKS:
        _one_up_projected_chunk_call_a_sublayer(text, cfg, chunk, smax)
    # ... to a hundredth: the per-slot state rides along, and what the
    # compiler hoists out of the chunk loop (a weight re-laid once an
    # admission, not once a chunk) stays live through it (37 MB of 6.6 GB
    # for the latent family; 57 MB of 14.0 GB at its cell's depth, under
    # the tick's own 14.43 GB: PERF.md 6).  A program that holds a ladder
    # (a chunk kernel's or a scan's call a width: GPT-2's upward, the
    # convolution family's downward, whose narrow pass's temporaries are
    # smaller than the chunk's and never live with them) is held to the
    # same hundredth against its WIDEST pass's ``extend``, GPT-2's at its
    # cell's own depth
    from deepspeed_tpu.serving.batcher import pass_widths
    widths = pass_widths(chunk, smax)
    # the cell's own geometry decides: 256 above a chunk of 128; half the
    # chunk below one of 1,024 in slots of 3,072, the one cell whose slot is
    # at most four chunks long (PR 62); the chunk alone in every other
    assert widths == {"dense": (256, 128), "moe": (256, 128),
                      "conv": (1024, 512)}.get(family, (chunk,))
    _one_call_a_layer_a_width(text, widths, chunk)
    if family in ("dense", "moe"):
        compiled, extend, pool, _ = admission_of(family, int8,
                                                 _LADDER_LAYERS)
    # ... that ``extend`` with its head over every row, which no pass of an
    # admission runs since PR 65: the admission holds no row of logits and
    # the yardstick still does, so the hundredth has more room under it than
    # it had (compiler, PR 65, admission / (extend + pool): latent 1.0097 ->
    # 1.0091, hybrid 1.0014 -> 1.0014, single-part 1.0003 -> 0.9945, window
    # 0.9889 -> 0.9871, linear 0.9991 -> 0.9991, conv 1.0058 -> 0.9873, GPT-2
    # at 24 layers 0.968 / 1.007 -> 0.968 / 0.972 bf16 / int8).  The
    # shortcut family's admission still hoists out of the chunk loop the
    # re-laid copies of ``wkv_a`` (``[4, 6144, 576]``: 576 is no whole number
    # of lane rows) and ``wkv_b`` (``[4, 512, 64, 256]``) of BOTH its
    # attention sublayers as whole stacks, where ``extend`` re-lays one layer
    # at a time (ROADMAP.md, Speed: S3 10), and reads 1.0099 -> 1.0056: the
    # room it was given apart (1.016) is not needed
    held_today = _planned_bytes(extend) + _pool_bytes(pool)
    assert _planned_bytes(compiled) <= 1.01 * held_today, (
        _planned_bytes(compiled), _planned_bytes(extend), _pool_bytes(pool))


def _one_call_a_layer_a_width(hlo_text, widths, chunk):
    """Every Mosaic kernel of an admission but the experts' grouped product
    (whose rows are pairs, not positions) is called as often at every
    width of the ladder: an ``extend`` body a width, a chunk kernel's or a
    scan's call a layer of it (where a ladder stops at the chunk and the row
    is a cache of its own, the program holds the ``prefill`` too: a kernel
    that only a ``prefill`` runs, its flash forward, at every width no wider
    than the chunk; GPT-2's bf16 admission works on the slot's own row and
    holds none: PR 63).  The
    width is read off the call's first result, the one dimension of it
    that is a width of the ladder."""
    import collections
    import re
    calls = collections.Counter()
    for name, dims in re.findall(
            r"%([a-zA-Z_]+)[.\d]* = \(?\w+\[([\d,]*)\][^\n]*"
            r"custom_call_target=\"tpu_custom_call\"", hlo_text):
        at = [int(d) for d in dims.split(",") if d and int(d) in widths]
        if name != "gmm":
            assert len(at) == 1, (name, dims)
            calls[name, at[0]] += 1
    assert calls, "no chunk kernel or scan in the admission"
    for name in {name for name, _ in calls}:
        by_width = {w: calls[name, w] for w in widths if calls[name, w]}
        assert set(by_width) in (set(widths),
                                 {w for w in widths if w <= chunk}), (
            name, by_width)
        assert len(set(by_width.values())) == 1, (name, by_width)


def _in_loops(hlo_text):
    """``(result dimensions, opcode)`` of every instruction a ``while`` of
    the module runs: its body's, and those of every computation the body
    calls (a fusion counted under its root's opcode, as ``_root_opcodes``
    counts it)."""
    import re
    bodies, calls, rows, roots, name = set(), {}, {}, {}, None
    line = re.compile(r"^\s*(ROOT )?%\S+ = \(?\w+\[([\d,]*)\]\S* ([\w-]+)\(")
    for text in hlo_text.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) \(.*\{$", text)
        if head:
            name = head.group(1)
            continue
        bodies |= set(re.findall(r"body=%([\w.]+)", text))
        called = re.findall(
            r"(?:calls|body|condition|to_apply)=%([\w.]+)", text)
        calls.setdefault(name, set()).update(called)
        m = line.match(text)
        if m:
            dims = tuple(int(d) for d in m.group(2).split(",") if d)
            fused = re.search(r"calls=%([\w.]+)", text)
            rows.setdefault(name, []).append(
                (dims, m.group(3), fused.group(1) if fused else None))
            if m.group(1):
                roots[name] = m.group(3)
    seen, todo = set(), list(bodies)
    while todo:
        c = todo.pop()
        if c not in seen:
            seen.add(c)
            todo += calls.get(c, ())
    return [(n, roots.get(fused, op) if op == "fusion" else op)
            for c in seen for n, op, fused in rows.get(c, ())]


def _one_up_projected_chunk_call_a_sublayer(text, cfg, chunk, smax):
    """An admission's latent chunk passes at the cells' widths (a chunk of
    512 or 1,024 positions, and its half where the ladder goes down: all
    over the 158 from which a key's up-projection pays,
    ``decode_attention.latent_up_projects``) run the UP-PROJECTED form: ONE
    custom call a sublayer body, twice a width in the program (the first
    chunk's ``prefill`` and the loop's ``extend``; under a ladder that
    goes down a fresh row starts empty and the program holds the
    ``extend`` alone), each with the one rank-3 result
    ``[heads, width, d_v]`` by which the benchmark's roofline reader finds
    it and no helper call beside it (a rank-2 result would read as a
    grouped matmul, a second rank-3 one would double the reader's calls);
    the absorbed chunk kernel is gone from the program.  That the program
    compiled says the kernel's scoped VMEM is under the 16 MiB a v5e's
    kernel may use (``latent_up_tiles`` sizes its step to 12; over the
    limit the compiler refuses the program).  ``W_kvb`` reaches the kernel
    as the head-major copy of its whole stack, made once an admission:
    inside the chunk loop nothing as large as ONE layer of it is copied or
    transposed."""
    import re

    from deepspeed_tpu.serving.batcher import pass_widths
    widths = pass_widths(chunk, smax)
    kernels = [(k, shape) for k, shape in _custom_calls(text)
               if k != "gmm"]
    H, rank, e = cfg.n_head, cfg.kv_rank, cfg.d_nope + cfg.d_v
    # a ``prefill`` and an ``extend`` where the ladder stops at the chunk;
    # under one that goes down a fresh row starts empty: an ``extend`` a
    # width and no ``prefill``
    bodies = 1 if widths[-1] < chunk else 2
    assert sorted(kernels) == sorted(
        [(decode.LATENT_UP_CHUNK, f"bf16[{H},{w},{cfg.d_v}]")
         for w in widths] * (2 * bodies)), kernels
    for w in widths:
        assert decode.latent_up_tiles(
            w, H, cfg.cache_row[0], rank, cfg.d_nope, cfg.d_v,
            decode.latent_block_k(smax)) is not None
    # the calls' last operand is a WHOLE stack, layers leading
    stacks = re.findall(
        rf"%{decode.LATENT_UP_CHUNK}[.\d]* = [^\n]*?bf16\[(\d+),{H},{rank},"
        rf"{e}\]\{{3,2,1,0\}}\}}, frontend_attributes", text)
    assert len(stacks) == 2 * bodies * len(widths), stacks
    # ... and no layer of it, in the stack's order or the head-major one
    # (but where that is the queries' own ``[heads, width, 256]``), is
    # copied or transposed inside a loop
    layers = {(rank, H, e)} | ({(H, rank, e)} - {(H, w, e) for w in widths})
    moved = [(dims, op) for dims, op in _in_loops(text)
             if tuple(d for d in dims if d > 1) in layers
             and op in ("copy", "transpose")]
    assert not moved, f"a layer's W_kvb is re-laid inside a loop: {moved}"


#: ``copy`` ops as large as a bank of the admission's batch-1 row, by
#: family, counted in the parent's program (the ragged scatter in the
#: slice's place, PR 49) at these geometries; every other family's has none.
#: The int8 cache's scale banks ``[L, 1, S, H]`` f32 ride the chunk loop
#: row-major and are re-laid once into it and once out of it (the pool
#: keeps them tokens-on-lanes), and at two layers the compiler also moves
#: the 2 MB code banks; the hybrid family's bank is its one attention
#: layer, so the chunk kernel's transposed reads of a layer are as large;
#: the single-part block's, the window family's and the convolution
#: family's two banks are gathered from the loop's carry for the slot write
#: (:data:`_KNOWN_MOVES`; the last's are 9.4 MB each), and the last
#: family's are copied once more into the ``lax.switch`` of its narrow last
#: pass (PR 62: a conditional copies a bank it writes; 46 us of an
#: admission of 40 ms).
_ROW_BANK_COPIES = {("dense", True): 4, ("moe", True): 6,
                    ("hybrid", False): 8, ("single_part", False): 2,
                    ("window", False): 2, ("conv", False): 4}


def _row_bank_ops(hlo_text, row_cache, opcode):
    """Instructions of ``opcode`` (a fusion under its root's) whose result
    is as large as a bank of ``row_cache`` (``k``, ``v`` and the int8
    cache's scale banks: what ``extend``'s ``write`` fills)."""
    banks = {b.size for b in (row_cache.k, row_cache.v, row_cache.k_scale,
                              row_cache.v_scale) if b is not None}
    return [n for n, op in _root_opcodes(hlo_text)
            if op == opcode and n in banks]


def _an_admissions_chunks_write_its_row_by_update_slices(
        admission_of, family, int8):
    """A further chunk of an admission lands in the batch-1 row cache by
    one update slice a bank a layer (``gpt_inference._chunk_slice``): the
    compiled program holds no ``scatter`` over a bank of that row (the
    ragged form, 17 us a bank a layer where the slice is one DMA), and the
    row keeps the pool's layout through the chunk loop: no bank of it is
    copied whole but where the parent's program copied one too.  The
    scatter used to pin that layout; left free, the compiler lays the row
    out tokens-on-lanes for the chunk kernel and re-lays every bank for
    the slot write."""
    compiled, _, _, row_cache = admission_of(family, int8, None)
    text = compiled.as_text()
    assert not _row_bank_ops(text, row_cache, "scatter")
    assert len(_row_bank_ops(text, row_cache, "copy")) <= \
        _ROW_BANK_COPIES.get((family, int8), 0)


#: ``(family, int8) -> (kernel calls, planned bytes)`` of the admissions
#: that keep the batch-1 row cache, at these geometries: every Mosaic call
#: by name and result as the PARENT of PR 63 compiled them (compiler, PR 63:
#: ``git archive aa89bde``, the same helpers), and what the program holds at
#: once as PR 65 left it (compiler, PR 65).  PR 63 gave the dense and
#: GPT-MoE families' bf16 admission a path of its own and left these the
#: program they had, instruction for instruction (the normalised text of
#: both trees' programs was compared once, by hand: ``PERF.md`` 6, PR 63);
#: PR 65 took the head out of every pass (no ``f32[1, rows, V]`` in any
#: body) and the plans fell where that array stood at the plan's peak: GPT-2
#: int8 516.8 -> 460.8 MB, GPT-MoE int8 574.8 -> 527.0, latent 6,557.9 ->
#: 6,550.0 (of which 3.7 MB is its cut's vocabulary, 2,048 -> 1,920), single
#: part 13,443.7 -> 13,366.2, window 15,069.3 -> 15,042.1, shortcut 14,834.4
#: -> 14,772.1, conv 14,598.1 -> 14,329.2 (its 268 MB of logits a 1,024-row
#: pass); where the peak lies inside a layer they moved by kilobytes the
#: other way (hybrid +15,872 B, linear +237,568 B: the row kept rides the
#: loops' carry).  What a later PR can hold them to without the parent's
#: tree is this.
_ROW_CACHE_ADMISSIONS = {
    ("dense", True): (
        {("chunk_attention", "bf16[16,1,128,64]"): 1,
         ("chunk_attention", "bf16[16,1,256,64]"): 1},
        460774400),
    ("moe", True): (
        {("chunk_attention", "bf16[16,1,128,64]"): 2,
         ("chunk_attention", "bf16[16,1,256,64]"): 2},
        526964224),
    ("latent", False): (
        {("gmm", "bf16[128,4096]"): 2,
         ("gmm", "bf16[128,7168]"): 2,
         ("latent_chunk_attention_up", "bf16[64,512,128]"): 4},
        6550024192),
    ("hybrid", False): (
        {("chunk_attention", "bf16[8,4,512,128]"): 2,
         ("gmm", "bf16[2560,1536]"): 6,
         ("gmm", "bf16[2560,4096]"): 6},
        13697177600),
    ("single_part", False): (
        {("chunk_attention", "bf16[2,16,1024,128]"): 4,
         ("gmm", "bf16[3072,1920]"): 8,
         ("gmm", "bf16[3072,2688]"): 8},
        13366165504),
    ("window", False): (
        {("chunk_attention", "bf16[4,8,1024,128]"): 8,
         ("gmm", "bf16[4096,1792]"): 8,
         ("gmm", "bf16[4096,2304]"): 8},
        15042128896),
    ("linear", False): (
        {("gmm", "bf16[2048,2048]"): 8,
         ("gmm", "bf16[2048,2304]"): 8,
         ("latent_chunk_attention_up", "bf16[32,1024,128]"): 4},
        13155315712),
    ("shortcut", False): (
        {("gmm", "bf16[256,4096]"): 2,
         ("gmm", "bf16[256,6144]"): 2,
         ("latent_chunk_attention_up", "bf16[64,512,128]"): 4},
        14772088320),
    ("conv", False): (
        {("chunk_attention", "bf16[8,4,1024,64]"): 1,
         ("chunk_attention", "bf16[8,4,512,64]"): 1,
         ("gmm", "bf16[2048,2048]"): 4,
         ("gmm", "bf16[2048,3584]"): 4,
         ("gmm", "bf16[4096,2048]"): 4,
         ("gmm", "bf16[4096,3584]"): 4},
        14329222656),
}


def _kernel_calls(hlo_text):
    """``{(kernel, result): calls}`` of a compiled module's Mosaic calls."""
    import collections
    return dict(collections.Counter(_custom_calls(hlo_text)))


def _an_admission_takes_the_path_its_cache_allows(admission_of, family,
                                                  int8):
    """Which of its two paths a family's admission compiled to
    (``gpt_inference.in_place``, decided by what the cache holds).

    ON THE SLOT'S OWN ROW (the dense and GPT-MoE families' bf16 pool): no
    row cache is made and none is written to the slot, so nothing in the
    whole program is as large as a bank of a batch-1 row: no ``broadcast``
    (the zero-fill), no ``copy``, ``dynamic-slice`` or ``transpose`` (the
    layer sliced out and re-laid heads-major around the chunk kernel), no
    ``scatter``; the chunk kernel is called once a layer of the body a
    width, on the folded queries, with the result ``bf16[1, width, H*D]``
    (rank 3 with a chunk's rows: one query row would read as a single-token
    sweep, ``ROADMAP.md`` S0 g2), its fifth and sixth operands the pool's
    banks whole; and the program holds no body of ``prefill`` (no flash
    forward): every pass is the ``extend``.  That the pool is aliased and
    nothing as large as a layer of it moves is ``one_program_in_place``'s.

    THROUGH THE ROW CACHE (every other row of :data:`_SERVED`): the
    program the parent compiled, by its kernel calls and its plan
    (:data:`_ROW_CACHE_ADMISSIONS`), and it still writes its row cache to
    the slot (the scope ``admit_slot_write``; the zero-fill's constants
    carry no scope's name)."""
    from deepspeed_tpu.models import cache_family
    from deepspeed_tpu.models.gpt_inference import in_place
    from deepspeed_tpu.serving.batcher import pass_widths
    init, cfg, slots, smax, chunk = _served(family)
    compiled, _, pool, row_cache = admission_of(family, int8, None)
    text = compiled.as_text()
    assert in_place(cache_family(cfg), pool) == (
        family in ("dense", "moe") and not int8)
    if (family, int8) in _ROW_CACHE_ADMISSIONS:
        calls, planned = _ROW_CACHE_ADMISSIONS[family, int8]
        assert _kernel_calls(text) == calls
        assert _planned_bytes(compiled) <= planned
        assert "/admit_slot_write/" in text
        return
    # ... read at the cell's own depth, where a bank of a row (25M elements)
    # is the size of nothing else in the program
    compiled, _, pool, row_cache = admission_of(family, int8, _LADDER_LAYERS)
    text = compiled.as_text()
    for opcode in ("broadcast", "copy", "dynamic-slice", "transpose",
                   "scatter"):
        assert not _row_bank_ops(text, row_cache, opcode), opcode
    assert "/admit_slot_write/" not in text
    HD = cfg.n_head * cfg.head_dim
    layers = 1 if family == "dense" else 2      # of a scan's body
    assert _kernel_calls(text) == {
        **{("chunk_attention", f"bf16[1,{w},{HD}]"): layers
           for w in pass_widths(chunk, smax)},
        **{k: n for k, n in _kernel_calls(text).items() if k[0] == "gmm"}}
    banks = re.findall(
        r"%chunk_attention[.\d]* = [^\n]*operand_layout_constraints=\{"
        r"(?:s32\[1\]\{0\}, ){3}bf16\[1,\d+,\d+\]\{2,1,0\}, "
        rf"bf16\[(\d+),{slots},{smax},{HD}\]\{{3,2,1,0\}}, "
        rf"bf16\[\1,{slots},{smax},{HD}\]\{{3,2,1,0\}}\}}", text)
    assert len(banks) == layers * len(pass_widths(chunk, smax)), banks


def _vocabulary_wide(hlo_text, vocab):
    """``(dims, opcode, op_name)`` of every instruction of a compiled module
    (inside its fusions too) one of whose result's dimensions is ``vocab``,
    tuples left out."""
    found = []
    for dims, opcode, rest in re.findall(
            r"^\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* ([\w-]+)\(([^\n]*)",
            hlo_text, re.M):
        dims = tuple(int(d) for d in dims.split(",") if d)
        if vocab in dims:
            name = re.search(r'op_name="([^"]*)"', rest)
            found.append((dims, opcode, name.group(1) if name else ""))
    return found


def _an_admission_runs_its_head_once_on_one_row(admission_of, family, int8):
    """An admission's passes return the layer stack's output and the head
    runs ONCE, after the last pass, on the one row kept (PR 65): in the
    compiled program nothing holds a vocabulary's logits for more than one
    row.  Every array with the padded vocabulary among its dimensions is
    one row's (``[V]``, ``[1, V]``), the slots' frontier logits ``[slots,
    V]`` (donated, one row of it written), or a matrix of the model's own
    with the vocabulary on its rows (the head, the embedding: ``[V, d]``,
    as a parameter or converted inside the product's fusion); the
    vocabulary-wide product is ONE instruction (a ``convolution``, or the
    multiply-and-``reduce`` the compiler writes a one-row product as), under
    the scope ``admit_head`` by which ``batcher.admit_head_share.decode``
    finds it, and no loop's body holds it.  Before, every pass's body held
    ``f32[1, rows, V]`` (268 MB at ``lfm2-serve-assist-sat``'s 1,024 rows
    of 65,536) to take one line of it."""
    from deepspeed_tpu.serving.batcher import pass_widths
    init, cfg, slots, smax, chunk = _served(family)
    compiled, _, _, _ = admission_of(family, int8, None)
    text = compiled.as_text()
    vocab = jax.tree_util.tree_leaves(compiled.out_info)[-1].shape[-1]
    assert vocab >= cfg.vocab_size and slots not in pass_widths(chunk, smax)
    matrices = {x.shape for x in jax.tree_util.tree_leaves(
        jax.eval_shape(init, jax.random.PRNGKey(0))) if vocab in x.shape}
    assert matrices and all(m[0] == vocab for m in matrices), matrices
    wide = _vocabulary_wide(text, vocab)
    rows = [(dims, op) for dims, op, _ in wide
            if dims not in matrices | {(slots, vocab)}
            and tuple(d for d in dims if d > 1) != (vocab,)]
    assert not rows, f"more than one row of logits: {sorted(set(rows))}"
    products = [(dims, op, name) for dims, op, name in wide
                if op in ("convolution", "dot", "reduce")
                and "dot_general" in name]
    assert len(products) == 1, products
    assert "/admit_head/" in products[0][2] \
        and "/while/" not in products[0][2], products
    assert not [dims for dims, _ in _in_loops(text)
                if vocab in dims and dims not in matrices], \
        "a loop's body holds a row of logits"


_ADMISSION_GUARDS = {
    "the_head_once_on_one_row": _an_admission_runs_its_head_once_on_one_row,
    "one_program_in_place": _admission_is_one_program_on_the_pool_in_place,
    "row_by_update_slices":
        _an_admissions_chunks_write_its_row_by_update_slices,
    "the_path_it_takes": _an_admission_takes_the_path_its_cache_allows}


@pytest.mark.parametrize("guard", list(_ADMISSION_GUARDS))
@_SERVED
def test_an_admission(admission_of, family, int8, guard):
    """The four guards that read one compile of a family's admission
    (``admission_of``, once a process), as cases of one function so that
    they run one after the other: apart, the scheduler hands them to two
    workers about every other run and each compiles the program (99 s the
    second time for one family in a whole run of this PR)."""
    _ADMISSION_GUARDS[guard](admission_of, family, int8)


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_the_update_slices_plan_is_the_scatters(v5e, admission_of,
                                                monkeypatch, int8):
    """... and the plan does not grow with the row: against the same
    admission with the parent's scatter in the slice's place it holds the
    chunk's rows more, read back and moved (0.1-0.7 MB), at the depth the
    ladder's program is served at (:data:`_LADDER_LAYERS`)."""
    from deepspeed_tpu.models import gpt_inference
    sliced = _planned_bytes(admission_of("dense", int8, _LADDER_LAYERS)[0])
    def scatter(bank, layer, val, pos0, row=None):
        # the parent's scatter; of one row of a pool where the bf16
        # admission works on the slot's own (PR 63)
        if row is None:
            return gpt_inference._chunk_scatter(bank, layer, val, pos0)
        return bank.at[layer, row, pos0[0] + jnp.arange(val.shape[1])].set(
            val[0])

    monkeypatch.setattr(gpt_inference, "_chunk_slice", scatter)
    scattered, _, pool, row_cache = _compile_admission(v5e, "dense", int8,
                                                       _LADDER_LAYERS)
    # the bf16 admission works on the slot's own row: its scatter's result
    # is a bank of the pool (PR 63); the int8 cache's a bank of its row
    assert _row_bank_ops(scattered.as_text(), row_cache if int8 else pool,
                         "scatter")
    # ... since PR 65 no pass holds a row of logits, and in the room that
    # left the compiler keeps the SCATTERED program's int8 row (51 MB at
    # this depth) whole in its fast memory (``S(1)`` on its four banks),
    # out of the plan; the slice names the row's layout and its row stays
    # where the pool is, as it did: the row is then the whole difference
    # (compiler, PR 65: 4,198.1 against 4,147.0 MB; before, 4,349.9 /
    # 4,349.2)
    fast = _pool_bytes(row_cache) if int8 else 0
    assert sliced <= _planned_bytes(scattered) + fast + (1 << 20)


@pytest.mark.parametrize("kernel", ["decode_step", "chunk_scan",
                                    "gqa_decode", "grouped_matmul"])
def test_the_single_part_cells_kernels_at_its_shapes(v5e, kernel):
    """The four kernels of ``nemotron3n-serve-agent-sat`` alone, at the
    cell's shapes: the state step and the chunk scan with 8 groups (64
    heads of 64, state 128, sub-chunks of 128: a column block of the scan
    holds two groups, the step's block all eight), the grouped-head decode
    at 16 query heads a key-value head over rows of 16,384 x 256, and the
    grouped matmul over 32 experts of ``[2688, 1920]`` and ``[1920,
    2688]``, read where they lie in an 8-layer stack (a chunk's 1,024 x 6
    pair rows; a tick's are 768)."""
    held, ssm = _KERNEL_MODULES[-2:]

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    L, slots, N, HP, G = 8, 128, 128, 4096, 8
    state = arg((L, slots, N, HP))
    if kernel == "decode_step":
        assert ssm._tiles(N, HP, G)
        _compiles_with_kernel(
            lambda st, x, decay, b, c, live: ssm.ssm_decode_step(
                st, 3, x, decay, b, c, active=live, groups=G),
            state, arg((slots, HP)), arg((slots, HP)),
            arg((slots, G * N), BF16), arg((slots, G * N), BF16),
            arg((slots,), jnp.bool_))
    elif kernel == "chunk_scan":
        row = arg((L, 1, N, HP))
        _compiles_with_kernel(
            lambda st, v, dt, a, b, c, n: ssm.ssd_chunk_scan(
                st, 3, v, dt, a, b, c, valid=n, chunk=128, groups=G),
            row, arg((1, 1024, 64, 64), BF16), arg((1, 1024, 64)),
            arg((64,)), arg((1, 1024, G * N), BF16),
            arg((1, 1024, G * N), BF16), arg((1,), jnp.int32))
    elif kernel == "gqa_decode":
        # agent-sat's sweep: blocks of 1,024 tokens of the 256-wide row
        # (PR 46), two banks x the pipeline's two blocks of 512 KiB beside
        # the (32, 256) float32 accumulator: under a quarter of the 16 MiB
        # a v5e's kernel may use
        from tests.unit.ops.traced_sweeps import sweep_calls
        smax, width, heads = 16384, 256, 32
        pool = arg((2, slots, smax, width), BF16)
        assert decode.decode_block_k(smax, width) == 1024
        sweep = lambda q, k, v, pos, live: decode.cached_attention(
            q, k, v, pos, sm_scale=128 ** -0.5, layer=1, active=live,
            kv_heads=2)
        shapes = (arg((slots, 1, heads, 128), BF16), pool, pool,
                  arg((slots,), jnp.int32), arg((slots,), jnp.bool_))
        assert sweep_calls(jax.make_jaxpr(sweep)(*shapes).jaxpr, slots, smax,
                           width) == [("gqa_decode_attention", 1024, 1024)]
        assert 2 * 2 * 1024 * width * 2 + heads * width * 4 < (16 << 20) // 4
        _compiles_with_kernel(sweep, *shapes)
    else:
        for k, n in ((2688, 1920), (1920, 2688)):
            assert all(side % tile == 0 for side, tile in zip(
                (k, n), held.gmm_tiling(k, n)[1:]))
            _compiles_with_kernel(
                lambda rows, w, sizes: held._grouped(rows, w, sizes, 3),
                arg((6144, k), BF16), arg((L, 32, k, n), BF16),
                arg((32,), jnp.int32))


@pytest.mark.parametrize("kernel", ["decode_step", "chunk_scan"])
def test_the_linear_cells_kernels_at_its_shapes(v5e, kernel):
    """The two delta-rule kernels of ``kimilin-serve-think-sat`` alone, at
    the cell's shapes: the step over 256 slots of a 6-layer stack (32 heads'
    128 x 128 tiles in one 4,096-lane block, ``q``, ``k`` and the decay as
    96 columns beside it), and the chunk scan of one row's 1,024-token
    chunk in sub-chunks of 64 (a column block holds 8 heads; the scores in
    two levels: sub-blocks of ``SUB_BLOCK`` rows, the pairs across
    sub-blocks as products through the row block's first row, the diagonal
    blocks a column at a time from whole sublane rows of 8 in one loop over
    the block's 8 heads; the inverse of ``I + A``
    by halves, 5 levels of two 64 x 64 products).  The scan is ONE custom call that returns
    the chunk's rows beside the aliased stack: the benchmark's reader
    (``state_kernels.kernel_of``) tells the kernel by that pair, and a
    second call would be left out of the time its roofline divides by."""
    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    L, slots, K, H = 6, 256, 128, 32
    assert delta_rule._tiles(K, K)
    if kernel == "decode_step":
        _compiles_with_kernel(
            lambda st, q, k, v, g, beta, live: delta_rule.kda_decode_step(
                st, 3, q, k, v, g, beta, active=live),
            arg((L, slots, K, H * K)), *[arg((slots, H, K))] * 4,
            arg((slots, H)), arg((slots,), jnp.bool_))
    else:
        text = _compiles_with_kernel(
            lambda st, q, k, v, g, beta, n: delta_rule.kda_chunk_scan(
                st, 3, q, k, v, g, beta, valid=n, chunk=64),
            arg((L, 1, K, H * K)), *[arg((1, 1024, H, K))] * 4,
            arg((1, 1024, H)), arg((1,), jnp.int32))
        calls = re.findall(r"%kda_chunk_scan\S* = (\(.*?\)) custom-call\(",
                           text)
        assert [re.sub(r"\{[^}]*\}", "", c) for c in calls] == [
            "(f32[1,1024,4096], f32[6,1,128,4096])"], calls
        assert text.count('custom_call_target="tpu_custom_call"') == 1
        assert "output_to_operand_aliasing={{1}: (8, {})}" in text


@pytest.mark.parametrize("kernel", ["decode_step", "chunk_scan",
                                    "gqa_decode", "grouped_matmul"])
def test_the_longctx_cells_kernels_at_its_shapes(v5e, kernel):
    """The kernels of ``solar2-serve-longctx-sat`` alone, at the cell's
    shapes (its chunk kernel is ``_CHUNK_PASSES``' ``longctx-sat``): the
    delta-rule step over 96 slots of a 3-layer stack at 64 heads (two column
    blocks of 4,096 lanes a slot, 32 heads each), the chunk scan of one
    row's 1,024-token chunk at 64 heads (8 column blocks of 8 heads, ONE
    custom call beside the aliased stack, as the benchmark's reader tells
    it), the grouped-head decode at 8 query heads a key-value head over
    rows of 16,384 x 1,024, and the grouped matmul over 40 experts of
    ``[4096, 2560]`` (640-wide columns under the whole contraction side) and
    ``[1280, 4096]`` (640 x 1,024 tiles), read where they lie in a 3-layer
    stack (a chunk's 1,024 x 8 x 40/320 pair rows; a tick's are 96)."""
    held = _KERNEL_MODULES[-2]

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    L, slots, K, H = 3, 96, 128, 64
    if kernel == "decode_step":
        assert delta_rule._tiles(K, K)
        _compiles_with_kernel(
            lambda st, q, k, v, g, beta, live: delta_rule.kda_decode_step(
                st, 2, q, k, v, g, beta, active=live),
            arg((L, slots, K, H * K)), *[arg((slots, H, K))] * 4,
            arg((slots, H)), arg((slots,), jnp.bool_))
    elif kernel == "chunk_scan":
        text = _compiles_with_kernel(
            lambda st, q, k, v, g, beta, n: delta_rule.kda_chunk_scan(
                st, 2, q, k, v, g, beta, valid=n, chunk=64),
            arg((L, 1, K, H * K)), *[arg((1, 1024, H, K))] * 4,
            arg((1, 1024, H)), arg((1,), jnp.int32))
        calls = re.findall(r"%kda_chunk_scan\S* = (\(.*?\)) custom-call\(",
                           text)
        assert [re.sub(r"\{[^}]*\}", "", c) for c in calls] == [
            "(f32[1,1024,8192], f32[3,1,128,8192])"], calls
        assert text.count('custom_call_target="tpu_custom_call"') == 1
    elif kernel == "gqa_decode":
        from tests.unit.ops.traced_sweeps import sweep_calls
        smax, width, heads = 16384, 1024, 64
        pool = arg((1, slots, smax, width), BF16)
        block = decode.decode_block_k(smax, width)
        sweep = lambda q, k, v, pos, live: decode.cached_attention(
            q, k, v, pos, sm_scale=128 ** -0.5, layer=0, active=live,
            kv_heads=8)
        shapes = (arg((slots, 1, heads, 128), BF16), pool, pool,
                  arg((slots,), jnp.int32), arg((slots,), jnp.bool_))
        assert sweep_calls(jax.make_jaxpr(sweep)(*shapes).jaxpr, slots, smax,
                           width) == [("gqa_decode_attention", block, block)]
        # two banks x the pipeline's two blocks beside the float32
        # accumulator: under half of the 16 MiB a v5e's kernel may use
        assert 2 * 2 * block * width * 2 + heads * width * 4 < (16 << 20) // 2
        _compiles_with_kernel(sweep, *shapes)
    else:
        assert held.gmm_tiling(4096, 2560) == (128, 4096, 640)
        assert held.gmm_tiling(1280, 4096) == (128, 640, 1024)
        for k, n in ((4096, 2560), (1280, 4096)):
            _compiles_with_kernel(
                lambda rows, w, sizes: held._grouped(rows, w, sizes, 2),
                arg((1024, k), BF16), arg((L, 40, k, n), BF16),
                arg((40,), jnp.int32))


# cell: (heads, key-value heads, D, chunk, keys of the call, window) ->
# (positions a query tile, keys a block)
_CHUNK_PASSES = {
    "code-sat-full": ((32, 4, 128, 1024, 8192, None), (128, 1024)),
    "code-sat-window": ((32, 4, 128, 1024, 2048, 1024), (128, 1024)),
    "agent-sat": ((32, 2, 128, 1024, 16384, None), (64, 1024)),
    "rag-sat": ((32, 8, 128, 512, 5120, None), (256, 1024)),
    "gpt2-medium": ((16, 16, 64, 128, 1024, None), (128, 512)),
    # 4 query heads of 64 a key-value head: a group's rows half a lane row
    "assist-sat": ((32, 8, 64, 1024, 3072, None), (256, 1024)),
    # 8 query heads of 128 a key-value head over a row of 16,384
    "longctx-sat": ((64, 8, 128, 1024, 16384, None), (128, 1024)),
    # a verify's few positions under grouped heads: 8-row tiles of a packed
    # dtype, a group's under one another
    "verify-8": ((32, 4, 128, 8, 2048, None), (8, 1024)),
    "verify-24": ((32, 4, 128, 24, 2048, None), (8, 1024)),
}


@pytest.mark.parametrize("cell", sorted(_CHUNK_PASSES))
def test_the_chunk_pass_at_the_cells_tiles(v5e, cell):
    """The chunk kernel alone at every serving cell's admission shape: a
    grid step is a key-value head's whole group against one key block,
    about a million scores (1,024 rows x 1,024 keys for 4, 8 and 16 query
    heads a key-value head: 4 MB of float32 scores beside their
    probabilities in the 16 MiB a v5e's kernel may use), and the grid and
    the blocks are the ones the rule gives for the operands."""
    from tests.unit.ops.traced_sweeps import _deep
    (H, Hkv, D, chunk, smax, window), (block_q, block_k) = _CHUNK_PASSES[cell]
    G = H // Hkv
    assert decode.chunk_block_k(smax) == block_k
    assert decode.chunk_block_q(chunk, G, block_k) == block_q
    assert G * block_q * block_k <= 1 << 20

    def arg(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    def pass_(q, k, v, pos, first):
        return decode.cached_attention(
            q, k, v, pos, kv_heads=Hkv, window=window,
            valid_from=first if window else None)

    shapes = (arg((1, chunk, H, D)), arg((1, smax, Hkv, D)),
              arg((1, smax, Hkv, D)), arg((1,), jnp.int32),
              arg((1,), jnp.int32))
    calls = [e for e in _deep(jax.make_jaxpr(pass_)(*shapes).jaxpr)
             if e.primitive.name == "pallas_call"]
    assert [e.params["name"] for e in calls] == ["chunk_attention"]
    assert tuple(calls[0].params["grid_mapping"].grid) == (
        Hkv, chunk // block_q, smax // block_k)
    assert calls[0].outvars[0].aval.shape == (Hkv, G, chunk, D)
    _compiles_with_kernel(pass_, *shapes)


@pytest.mark.parametrize("stochastic", [False, True],
                         ids=["nearest", "stochastic"])
def test_symmetric_quantizer(v5e, stochastic):
    w = jax.ShapeDtypeStruct((1024, D_FF), jnp.float32, sharding=v5e)
    _compiles_with_kernel(
        lambda w: quantizer.quantize(w, groups=1024, stochastic=stochastic),
        w)


def test_bias_gelu_dropout_forward_backward(v5e):
    x = jax.ShapeDtypeStruct((B * S, D_FF), BF16, sharding=v5e)
    b = jax.ShapeDtypeStruct((D_FF,), BF16, sharding=v5e)

    def loss(x, b):
        return jnp.sum(gelu.bias_gelu_dropout(x, b, dropout_rate=0.1, seed=3)
                       .astype(jnp.float32))

    _compiles_with_kernel(jax.grad(loss, argnums=(0, 1)), x, b)


# The train step's loss head (PERF.md 6, PR 39): the engine's own fused step
# at the two train cells' widths, micro-batch and vocabulary, two layers deep
# (the scan over layers compiles its body once whatever the depth).
_HEAD_CELLS = {
    "gpt2m-train-s1024": (("gpt2-medium", "builders.gpt2"), 24, 1024,
                          dict(chips=1, zero_stage=1)),
    "opt1b3-train-zero3-4chip": (("opt-1.3b", "builders.opt"), 8, 2048,
                                 dict(chips=4, zero_stage=3)),
    # no cell: tensor parallelism shards the vocabulary the logsumexp
    # reduces over (micro-batch 16, so that the logits are not small)
    "gpt2m-dp2-tp2": (("gpt2-medium", "builders.gpt2"), 16, 1024,
                      dict(chips=4, tp=2, zero_stage=3)),
}


_STEPS = {}


def _compiled_step(cell):
    """``(compiled, config)`` of the engine's fused step of a ``_HEAD_CELLS``
    entry for the described host, compiled once a process."""
    import dataclasses
    import json
    import sys
    from benchmarks.chip.builders import resolve
    if cell in _STEPS:
        return _STEPS[cell]
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    sys.path.insert(0, os.path.join(root, "scripts"))
    try:
        aot = importlib.import_module("aot_train_step")
    finally:
        sys.path.remove(os.path.join(root, "scripts"))
    (name, builder), micro, seq, how = _HEAD_CELLS[cell]
    with open(os.path.join(root, "benchmarks", "chip", "configs",
                           name + ".json")) as f:
        cfg = dataclasses.replace(
            resolve(builder)(json.load(f)), n_layer=2, max_seq_len=seq,
            dtype=BF16, remat=True, remat_policy="attn_out")
    _STEPS[cell] = aot.compile_step(cfg, micro, **how), cfg
    return _STEPS[cell]


def _train_step_holds_three_head_products_and_no_whole_logits(cell):
    """The compiler is handed the head a chunk of the sequence at a time
    under a backward rule of its own, so it re-makes nothing: three
    vocabulary-sized products a chunk (the logits and the two gradients), no
    ``.remat`` copy of any, no buffer the size of the float32 ``[B, S, V]``
    logits in the plan; and under ZeRO-3 the head is gathered ONCE, before
    the chunk loops."""
    import re
    _, micro, seq, how = _HEAD_CELLS[cell]
    compiled, cfg = _compiled_step(cell)
    text, vocab = compiled.as_text(), cfg.padded_vocab
    lines = text.splitlines()

    products = [ln for ln in lines
                if " convolution(" in ln and re.search(r'op_name="[^"]*/head/',
                                                       ln)]
    assert len(products) == 3, products
    remade = [ln for ln in lines if re.match(r"\s*%\S*\.remat\d* = ", ln)
              and (re.search(rf"= [^(]*\b{vocab}\b", ln)
                   or re.search(r'op_name="[^"]*/(head|loss)/', ln))]
    assert not remade, remade
    tp = how.get("tp", 1)
    whole = micro * seq * (vocab // tp) * 4
    assert f"f32[{micro},{seq},{vocab // tp}]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < whole

    entry = next(i for i, ln in enumerate(lines) if ln.startswith("ENTRY "))
    gathers = [i for i, ln in enumerate(lines) if re.search(
        rf"= bf16\[{vocab // tp},{cfg.d_model}\]\S* all-gather\(", ln)]
    if how["chips"] == 1:
        assert not gathers
    else:   # one, as before this head ran in chunks, and outside the loops
        assert len(gathers) == 1 and gathers[0] > entry, gathers


def _train_step_relays_nothing_around_the_flash_kernels(cell):
    """Between the qkv product and the output product of a layer nothing is
    re-laid (PERF.md 6, PR 43): the kernels' operands are the packed product
    as its projection wrote it, three times over, and rows that the output
    product reads.  The compiled step holds no array of the heads-major or
    the ``[B,S,H,D]`` form at all (the compiler lays a minor dimension of 64
    out tokens-on-lanes and copies it to and from every Mosaic call), no
    ``copy`` / ``transpose`` / slice fusion in the scope ``attention``, no
    float32 ``[., nk, S, D]`` partial of ``dq`` and no reduction there; what
    remains in the scope besides the two kernels is the join of ``dq, dk,
    dv`` into the packed product's gradient."""
    import re
    _, micro, seq, _ = _HEAD_CELLS[cell]
    compiled, cfg = _compiled_step(cell)
    text = compiled.as_text()
    h, d = cfg.n_head, cfg.head_dim
    for dims in ((micro, h, seq, d), (micro, seq, h, d),
                 (micro, seq, 3, h, d)):
        assert "[" + ",".join(map(str, dims)) + "]" not in text, dims
    assert not re.search(rf"f32\[{micro * h},\d+,{seq},{d}\]", text)
    scope = [ln for ln in text.splitlines()
             if re.search(r'op_name="[^"]*/attention/', ln)]
    relaid = [ln for ln in scope if re.search(
        r" (copy|transpose|reduce)\(|%\S*(slice_bitcast|transpose)\S* = ", ln)]
    assert not relaid, relaid
    calls = re.findall(r"%\S*(flash_\w+?)[_.\d]* = (.*?) custom-call\((.*?)\), "
                       r"custom_call_target=\"tpu_custom_call\"", text)
    assert sorted(name for name, _, _ in calls) == ["flash_bwd", "flash_fwd"]
    for name, result, operands in calls:
        qkv = operands.split(", ")[2:5]     # after kv_lens and window
        assert len(set(qkv)) == 1, (name, qkv)      # ONE array, three times
        assert f"bf16[{micro},{seq},{h * d}]" in result, (name, result)


_STEP_GUARDS = {
    "three_head_products_and_no_whole_logits":
        _train_step_holds_three_head_products_and_no_whole_logits,
    "nothing_relaid_around_the_flash_kernels":
        _train_step_relays_nothing_around_the_flash_kernels}


@pytest.mark.parametrize("cell,guard", [
    (cell, guard) for cell in _HEAD_CELLS for guard in _STEP_GUARDS
    # the kernels' operands are read in the two train cells' steps
    if cell != "gpt2m-dp2-tp2" or guard.startswith("three")])
def test_the_train_step(v5e_host, cell, guard):
    """The guards that read one compile of a cell's fused step
    (:func:`_compiled_step`, once a process), a cell's one after the
    other."""
    _STEP_GUARDS[guard](cell)


# --------------------------------- the selecting family at its own geometry

def _moves_of_a_pool(text, pool):
    """``(elements, opcode)`` of every copy, transpose or slice of the
    compiled module that is as large as a layer of one of the pool's stacks
    (their sizes: the three kinds of cached state differ)."""
    layers = {x.size // x.shape[0]
              for x in jax.tree_util.tree_leaves(pool) if x.ndim >= 4}
    return [(n, op) for n, op in _root_opcodes(text)
            if any(n % layer == 0 and n // layer <= 8 for layer in layers)
            and (op.startswith("copy") or op in ("transpose",
                                                 "dynamic-slice"))]


#: the selecting family's admission against its chunk's ``extend`` on the
#: batch-1 row and the pool (compiler, PR 60): 14,632,959,488 bytes planned
#: beside 7,092,738,560 + 6,747,586,560, 1.0573 of them; the parent's, every
#: chunk call absorbed, 14,319,682,560 beside 6,829,290,496 + 6,747,586,560,
#: 1.0547.  Both hold what the other families' hundredth does not: the first
#: chunk's ``prefill`` beside the loop's ``extend``, each with a selection's
#: ``[1024, 16384]`` scores, keys and bias.  The 313 MB between them are the
#: head-major stacks (3 x 33.5 + 6 x 50.3 MB, made by ``prefill`` and by the
#: loop: ROADMAP S3 10) less the absorbed queries and results.  With no row
#: of logits in any pass (PR 65) it plans 14,631,591,936, 1.0572: its peak
#: held none
_SELECTED_ROOM = 1.06


@pytest.mark.parametrize("program", [
    "tick", pytest.param("admission", marks=pytest.mark.slow)])
def test_the_selecting_family_leaves_its_three_pools_in_place(v5e, program):
    """The tick and the admission of the selected-latent / window-latent
    family at its cell's geometry, for the described chip: the index's two
    score kernels, the latent sweep under a bias and, in the admission, the
    UP-PROJECTED chunk kernel under one lower, the donated pool is updated
    in place, and nothing copies, transposes or slices as much as a layer of
    any of the three stacks: the latent bank, the index keys, the rings.
    The index's work list is built once a tick, outside the layer scans.

    Every latent chunk pass of this family carries a bias (a selection, a
    ring's band) and, at its cell's chunk of 1,024, up-projects its keys and
    values all the same (``latent_up_projects`` at each kind's widths, a
    window layer's 192-wide key part in 256 lanes): one call a layer of each
    scan's body, twice in the program (the first chunk's ``prefill`` and the
    loop's ``extend``), each on a WHOLE head-major stack with the bias as an
    int8 mask its last operand; the absorbed chunk kernel and the absorbed
    queries ``[chunk, heads, lanes]`` are gone from the program.  The TICK
    holds no up-projected call: one query a row amortises nothing."""
    from deepspeed_tpu.models import cache_family, latent_moe
    # ``dots3n-serve-longgen-sat``: the nine layers of its cut at 80 x
    # 16,384 in chunks of 1,024, three kinds of cached state (a 640-lane
    # latent bank and a 128-wide bank of index keys on three layers, a ring
    # of 640 cells of 1,152 lanes on six)
    init, cfg, slots, smax, chunk = _served("selected")
    fam = cache_family(cfg)
    params = _described(jax.eval_shape(init, jax.random.PRNGKey(0)), v5e)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    if program == "tick":
        pool = _described(jax.eval_shape(
            lambda: fam.init_cache(cfg, slots, smax)), v5e)
        tick = jax.jit(
            lambda p, c, tok, lengths, active: fam.decode_step(
                p, tok, cfg, c, lengths=lengths, active=active),
            donate_argnums=(1,))
        args = (params, pool, arg((slots,), jnp.int32),
                arg((slots,), jnp.int32), arg((slots,), jnp.bool_))
        _sweep_is_built_outside_the_layer_scan(
            tick.trace(*args).jaxpr, slots,
            segments=_segments(fam, cfg, params))
        compiled = tick.lower(*args).compile()
        text = compiled.as_text()
        kernels = ("index_decode_scores", "latent_decode_attention")
        assert decode.LATENT_UP_CHUNK not in text
    else:
        compiled, extend, pool, _ = _compile_admission(v5e, "selected", False)
        text = compiled.as_text()
        kernels = ("index_chunk_scores", decode.LATENT_UP_CHUNK)
        assert "/latent_chunk_attention/pallas_call" not in text
        from deepspeed_tpu.serving.batcher import pass_widths
        full, window = cfg.dims("full"), cfg.dims("window")
        # a pass: the dense first layer's call, then the unit's four
        a_pass = [full, full, window, window, window]
        # ... a ``prefill`` and an ``extend`` a width of the ladder, which
        # in slots of sixteen chunks is the chunk alone
        widths = pass_widths(chunk, smax)
        assert widths == (1024,)
        calls = [shape for k, shape in _custom_calls(text)
                 if k == decode.LATENT_UP_CHUNK]
        assert sorted(calls) == sorted(
            f"bf16[{dm.n_head},{w},{dm.d_v}]" for dm in 2 * a_pass
            for w in widths), calls
        for dm, n in ((full, 4), (window, 6)):
            e = latent_moe.lane_rows(dm.d_nope) + dm.d_v
            for w in widths:
                assert decode.latent_up_tiles(
                    w, dm.n_head, dm.lanes, dm.kv_rank, e - dm.d_v, dm.d_v,
                    128, biased=True) is not None
                # the stack goes in whole, layers leading, the mask after
                stacks = re.findall(
                    rf"bf16\[(\d+),{dm.n_head},{dm.kv_rank},{e}\]"
                    rf"\{{3,2,1,0\}}, s8\[1,{w},\d+\]\{{2,1,0\}}\}}, "
                    rf"frontend_attributes", text)
                assert len(stacks) == n, (dm, w, stacks)
                # ... and no absorbed query array is built anywhere
                assert f"{w},{dm.n_head},{dm.lanes}]" not in text
                assert f"[{w * dm.n_head},{dm.lanes}]" not in text
            # no layer of the stack is re-laid inside a loop
            relaid = [(dims, op) for dims, op in _in_loops(text)
                      if tuple(d for d in dims if d > 1) in (
                          (dm.kv_rank, dm.n_head, e), (dm.n_head, dm.kv_rank, e))
                      and op in ("copy", "transpose")]
            assert not relaid, relaid
        assert _planned_bytes(compiled) <= _SELECTED_ROOM * (
            _planned_bytes(extend) + _pool_bytes(pool)), (
                _planned_bytes(compiled), _planned_bytes(extend),
                _pool_bytes(pool))
    for kernel in kernels:
        assert f"/{kernel}/pallas_call" in text, kernel
    moved = _moves_of_a_pool(text, pool)
    assert not moved, f"the {program} moves whole layers of a pool: {moved}"
    assert not _pair_rows(text, cfg, params,
                          slots if program == "tick" else chunk), \
        f"the {program} builds rows for the pairs held elsewhere"
    assert "input_output_alias" in text
    assert compiled.memory_analysis().alias_size_in_bytes >= \
        _pool_bytes(pool), "the donated pool is not updated in place"
    # weights and pool are 12.9 GB of the chip's 15.75 GiB; the program's
    # own temporaries fit beside them
    plan = compiled.memory_analysis()
    assert plan.argument_size_in_bytes + plan.temp_size_in_bytes \
        < 15.75 * 2 ** 30
