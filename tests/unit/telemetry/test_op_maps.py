"""Device time by program and scope: the op-map parser on a committed
optimised-HLO text, a registered program's own map on the CPU, the join of
plain tuples, the published table's lifetime, and the operator's window."""

import gc
import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax import lax

from deepspeed_tpu.telemetry import device_time, op_maps
from deepspeed_tpu.telemetry.spans import Tracer
from deepspeed_tpu.utils.compile_watch import (CompiledProgramRegistry,
                                               registries)

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(autouse=True)
def clean_table():
    op_maps.clear_published()
    yield
    op_maps.clear_published()


# ------------------------------------------------------------- the parser

@pytest.mark.parametrize("op_name,scope,flags", [
    ("jit(f)/jvp()/while/body/closed_call/mlp/dot_general", "mlp", ()),
    ("jit(f)/transpose(jvp())/while/body/closed_call/checkpoint/mlp/norm/mul",
     "mlp/norm", ("backward",)),
    ("jit(f)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/attention/tanh", "attention",
     ("backward", "remat")),
    ("jit(f)/jvp(loss)/reduce_sum", "loss", ()),
    ("jit(f)/transpose(jvp(loss))/add_any", "loss", ("backward",)),
    ("jit(f)/jvp(jit(loss))/mul", "", ()),      # a function's name, no scope
    ("jit(f)/while/body/closed_call/bshe,hed->bsd/dot_general", "", ()),
    ("jit(admit)/admit_bind/jit(_threefry_fold_in)/admission.<locals>.admit/"
     "add", "admit_bind", ()),
    ("jit(f)/while/body/closed_call/cache_update/squeeze;cache_update/"
     "reshape;squeeze", "cache_update", ()),
    ("jit(f)/while/body/dynamic_slice;mlp/add", "mlp", ()),
    ("jit(f)/cache_read/decode_attention/pallas_call",
     "cache_read/decode_attention", ()),
    ("jit(f)/cond/branch_1_fun/optimizer/sub", "optimizer", ()),
    ("x", "", ()),
    ("", "", ()),
])
def test_scope_of_an_op_name(op_name, scope, flags):
    assert op_maps.scope_of(op_name) == (scope, flags)


def _fixture_rows():
    with open(os.path.join(HERE, "op_map_fixture.hlo.txt")) as f:
        return {r["name"]: r for r in op_maps.parse_op_map(f.read())}


@pytest.mark.parametrize("name,opcode,shape,scope,flags", [
    # a Mosaic custom call, in the scan's body, under its kernel's name
    ("flash_fwd.6", "custom-call", "bf16[8,16]", "attention/flash_fwd", []),
    # an instruction without metadata takes its operand's producer's scope
    ("copy.7", "copy", "bf16[8,16]", "attention/flash_fwd", ["copied"]),
    # ... and none where the operand is the loop's own parameter
    ("bitcast.8", "bitcast", "bf16[16,16]", "-", []),
    # a fusion whose instructions agree (the einsum's name is no scope)
    ("fusion.404", "fusion", "bf16[8,16]", "mlp", []),
    # one that spans scopes: all of them, the largest result's first, and
    # not the loop's own slicing beside them
    ("convert_reduce_fusion.2", "fusion", "(f32[8], bf16[8,16])",
     "attn_out+mlp/norm", []),
    # the scan's stacking update slice: the scope of the value it stacks
    ("bitcast_dynamic-update-slice_fusion.21", "fusion", "bf16[2,8,16]",
     "mlp", ["stacked"]),
    ("multiply_fusion.5", "fusion", "bf16[8,16]", "mlp",
     ["backward", "remat"]),
    ("slice-start.1", "slice-start",
     "((bf16[2,16,16]), bf16[1,16,16], s32[])", "-", []),
    ("add.12", "add", "s32[]", "-", []),
    ("lt.1", "compare", "pred[]", "-", []),                # the condition
    ("multiply.20", "multiply", "bf16[8,16]", "loss", []),  # the entry
])
def test_the_op_map_of_a_committed_hlo_text(name, opcode, shape, scope,
                                            flags):
    row = _fixture_rows()[name]
    assert (row["opcode"], row["shape"], row["scope"], row["flags"]) == (
        opcode, shape, scope, flags)


def test_a_fused_computations_instructions_are_no_rows():
    rows = _fixture_rows()
    # fused bodies, a reduce's scalar function, parameters, constants and
    # tuples never show in a trace as events of their own
    assert not {"convolution.1", "tanh.1", "add.9", "reduce.3", "params.1",
                "constant.8", "tuple.9", "get-tuple-element.2"} & set(rows)
    assert all(set(r) == {"name", "opcode", "shape", "scope", "flags"}
               for r in rows.values())
    json.dumps(list(rows.values()))                         # plain data


def test_an_asynchronous_pair_joins_under_either_spelling():
    maps = [{"registry": "r", "program": "p",
             "ops": list(_fixture_rows().values())}]
    shape = "((bf16[2,16,16]), bf16[1,16,16], s32[])"
    ops = [(0, "slice-start.1", "async-start", shape, 0.0, 1.0),
           (0, "slice-done.1", "async-done", "bf16[1,16,16]", 1.0, 2.0)]
    table, unjoined = device_time(ops, maps)
    assert table == {("p", "-"): 2.0} and unjoined == 0.0


# ------------------------------------------------ a registered program

def _layer(x, p):
    with jax.named_scope("attention"):
        a = jnp.tanh(x @ p["wa"])
    with jax.named_scope("mlp"):
        m = jax.nn.gelu(a @ p["wi"]) @ p["wo"]
    return x + m


def _step(params, x, lr):
    def loss(params):
        y, _ = lax.scan(lambda x, p: (jax.checkpoint(_layer)(x, p), None),
                        x, params)
        with jax.named_scope("loss"):
            return jnp.sum(y * y)
    value, grads = jax.value_and_grad(loss)(params)
    with jax.named_scope("optimizer"):
        return value, jax.tree_util.tree_map(lambda p, g: p - lr * g,
                                             params, grads)


def _params(layers=3, d=16):
    return {"wa": jnp.ones((layers, d, d)), "wi": jnp.ones((layers, d, 2 * d)),
            "wo": jnp.ones((layers, 2 * d, d))}, jnp.ones((4, d))


def test_a_registered_program_knows_the_scope_of_each_of_its_ops():
    reg = CompiledProgramRegistry("t-engine", tracer=Tracer(enabled=True))
    step = reg.register("step", jax.jit(_step, donate_argnums=(0,)))
    params, x = _params()
    _, params = step(params, x, 0.1)        # a Python scalar: a weak type
    assert step._cache_size() == 1
    (published,) = op_maps.published()
    assert (published["registry"], published["program"]) == (
        "t-engine", "step")
    rows = published["ops"]
    by = lambda scope, flag: [r for r in rows if r["scope"] == scope
                              and flag in r["flags"]]
    scopes = {r["scope"] for r in rows}
    assert {"attention", "mlp", "loss", "optimizer"} <= scopes
    assert by("mlp", "backward") and by("attention", "backward")
    assert by("mlp", "remat")               # the checkpoint's re-forward
    # the backward scan stacks each layer's gradients: compiler-made update
    # slices, named by what they stack
    assert by("mlp", "stacked") and by("attention", "stacked")
    assert all("backward" not in r["flags"] for r in rows
               if r["scope"] == "optimizer")
    # building it again lowers the same program: the jit cache stands
    before = len(reg.events)
    assert reg.op_map("step") == rows
    assert step._cache_size() == 1 and len(reg.events) == before
    # a second call compiles nothing, so builds nothing
    published["ops"] = "sentinel"
    step(params, x, 0.1)
    assert op_maps.published()[0]["ops"] == "sentinel"
    assert step._cache_size() == 1


@pytest.mark.parametrize("committed", [False, True],
                         ids=["uncommitted", "committed"])
def test_the_description_lowers_the_same_module(committed):
    """An argument described with a sharding it was never committed to
    would be annotated in the lowering: another module, whose compiler-made
    names (``fusion.195``) are not the running program's."""
    from deepspeed_tpu.utils.compile_watch import _described
    f = jax.jit(lambda p, x, lr: (p["w"] @ x) * lr, donate_argnums=(1,))
    x = jnp.ones((4, 4))
    p = {"w": jax.device_put(x + 1, jax.devices()[0]) if committed else x + 1}
    assert p["w"].committed == committed
    ran = f.lower(p, x, 0.5).as_text()
    f(p, x, 0.5)                            # ``x`` is donated: deleted
    args, kwargs = _described((p, x, 0.5), {})
    assert args[1].shape == (4, 4) and args[2] == 0.5 and not kwargs
    assert f.lower(*args).as_text() == ran


def test_the_newest_compile_wins():
    reg = CompiledProgramRegistry("t-new", tracer=Tracer(enabled=True))
    f = reg.register("f", jax.jit(lambda x: jnp.tanh(x) + 1))
    f(jnp.ones((4,)))
    first = op_maps.published()[0]["ops"]
    f(jnp.ones((8, 2)))                     # another shape: a new compile
    (published,) = op_maps.published()
    assert published["ops"] != first
    assert any("[8,2]" in r["shape"] for r in published["ops"])


def test_the_table_outlives_its_registry_and_its_owner():
    class Owner:
        def __init__(self):
            self.tracer = Tracer(enabled=True)
            self.registry = CompiledProgramRegistry("t-owner",
                                                    tracer=self.tracer)
            self.f = self.registry.register(
                "f", jax.jit(lambda x: jnp.sin(x) * 2))
    owner = Owner()
    owner.f(jnp.ones((4,)))
    assert any(r.name == "t-owner" for r in registries())
    del owner
    gc.collect()
    assert not any(r.name == "t-owner" for r in registries())
    (published,) = op_maps.published()
    assert published["program"] == "f" and published["ops"]
    ops = [(0, r["name"], r["opcode"], r["shape"], float(i), i + 1.0)
           for i, r in enumerate(published["ops"])]
    table, unjoined = device_time(ops)      # the published table by default
    assert unjoined == 0.0 and sum(table.values()) == len(ops)


@pytest.mark.parametrize("tracer", [None, Tracer(enabled=False)],
                         ids=["no-tracer", "tracer-off"])
def test_a_registry_with_the_tracer_off_publishes_nothing(tracer):
    reg = CompiledProgramRegistry("t-off", tracer=tracer)
    f = reg.register("f", jax.jit(lambda x: x * 2))
    f(jnp.ones((4,)))
    assert op_maps.published() == []
    assert reg.op_map("g") is None          # never compiled
    # ... and on demand, from the shapes its last compile left
    assert reg.publish_op_maps() == ["f"]
    assert [m["program"] for m in op_maps.published()] == ["f"]
    assert f._cache_size() == 1


def test_an_op_map_that_cannot_be_built_stops_nothing(monkeypatch):
    reg = CompiledProgramRegistry("t-broken", tracer=Tracer(enabled=True))
    f = reg.register("f", jax.jit(lambda x: x + 1))
    monkeypatch.setattr(op_maps, "parse_op_map",
                        lambda text: 1 / 0)
    assert float(f(jnp.ones(()))) == 2.0
    assert op_maps.published() == []


def test_re_registering_a_name_forgets_the_old_programs_shapes():
    reg = CompiledProgramRegistry("t-again")
    reg.register("f", jax.jit(lambda x: x + 1))(jnp.ones((4,)))
    reg.register("f", jax.jit(lambda x, y: x + y))
    assert reg.publish_op_maps() == []


# ------------------------------------------------------------- the join

def _maps():
    row = lambda name, shape, scope: {
        "name": name, "opcode": "fusion", "shape": shape, "scope": scope,
        "flags": []}
    return [
        {"registry": "serving", "program": "tick", "ops": [
            row("fusion.1", "bf16[8]", "mlp"),
            row("fusion.7", "bf16[4]", "cache_update"),     # both hold it
            row("fusion.9", "bf16[2]", "sample")]},
        {"registry": "serving", "program": "admit", "ops": [
            row("fusion.2", "bf16[8]", "admit_chunk/mlp"),
            row("fusion.7", "bf16[4]", "admit_slot_write"),
            row("fusion.9", "bf16[2]", "admit_bind")]}]


def _op(device, name, shape, start, end):
    return (device, name, "fusion", shape, start, end)


def test_an_ambiguous_op_takes_the_program_of_its_neighbours():
    ops = [_op(0, "fusion.1", "bf16[8]", 0.0, 1.0),     # tick
           _op(0, "fusion.7", "bf16[4]", 1.0, 3.0),     # between two ticks
           _op(0, "fusion.1", "bf16[8]", 3.0, 4.0),
           _op(0, "fusion.2", "bf16[8]", 4.0, 5.0),     # admit
           _op(0, "fusion.7", "bf16[4]", 5.0, 8.0),     # inside the admit
           _op(0, "fusion.2", "bf16[8]", 8.0, 9.0)]
    table, unjoined = device_time(ops, _maps())
    assert unjoined == 0.0
    assert table == {("tick", "mlp"): 2.0, ("tick", "cache_update"): 2.0,
                     ("admit", "admit_chunk/mlp"): 2.0,
                     ("admit", "admit_slot_write"): 3.0}


def test_what_cannot_be_placed_is_unjoined_never_guessed():
    ops = [_op(0, "fusion.1", "bf16[8]", 0.0, 1.0),     # tick
           _op(0, "fusion.7", "bf16[4]", 1.0, 2.0),     # tick or admit?
           _op(0, "fusion.2", "bf16[8]", 2.0, 3.0),     # admit
           _op(0, "fusion.33", "bf16[8]", 3.0, 3.5),    # no program's
           _op(0, "fusion.1", "bf16[16]", 3.5, 3.75)]   # another shape
    table, unjoined = device_time(ops, _maps())
    assert unjoined == pytest.approx(1.0 + 0.5 + 0.25)
    assert table == {("tick", "mlp"): 1.0, ("admit", "admit_chunk/mlp"): 1.0}
    # an ambiguous op beside one unambiguous neighbour takes its program
    table, unjoined = device_time(ops[1:3], _maps())
    assert unjoined == 0.0 and table[("admit", "admit_slot_write")] == 1.0
    # ... and with none, stays unjoined
    table, unjoined = device_time(ops[1:2], _maps())
    assert table == {} and unjoined == 1.0


def test_a_neighbour_on_another_device_places_nothing():
    ops = [_op(0, "fusion.1", "bf16[8]", 0.0, 1.0),
           _op(1, "fusion.7", "bf16[4]", 1.0, 2.0)]
    table, unjoined = device_time(ops, _maps())
    assert table == {("tick", "mlp"): 0.5} and unjoined == 0.5


def test_two_devices_are_averaged():
    one = [_op(0, "fusion.1", "bf16[8]", 0.0, 1.0),
           _op(0, "fusion.9", "bf16[2]", 1.0, 1.5)]
    two = [_op(1, n, s, a + 0.25, b + 0.25) for _, n, _, s, a, b in one]
    table, unjoined = device_time(one + two, _maps())
    assert table == {("tick", "mlp"): 1.0, ("tick", "sample"): 0.5}
    assert unjoined == 0.0
    assert device_time([], _maps()) == ({}, 0.0)


def test_the_report_lists_programs_scopes_and_the_unjoined_share():
    ops = [_op(0, "fusion.1", "bf16[8]", 0.0, 3.0),
           _op(0, "fusion.9", "bf16[2]", 3.0, 4.0),
           _op(0, "fusion.33", "bf16[8]", 4.0, 5.0)]
    text = "\n".join(op_maps.format_report(ops, _maps()))
    assert "tick" in text and "mlp" in text and "60.00%" in text
    assert "unjoined" in text and "20.00%" in text
    assert op_maps.root_scope("attn_out+mlp/norm") == "attn_out"
    assert "no device operation" in op_maps.format_report([], _maps())[0]


# ------------------------------------------------- the operator's window

def test_a_capture_window_writes_the_programs_beside_the_trace(tmp_path,
                                                               capsys):
    from deepspeed_tpu.telemetry import profiler_trace
    from scripts import run_report
    reg = CompiledProgramRegistry("t-window")           # its tracer is off
    f = reg.register("f", jax.jit(lambda x: jnp.cos(x) + 1))
    logdir = str(tmp_path / "trace")
    with profiler_trace(logdir):
        jax.block_until_ready(f(jnp.ones((8,))))
    programs = op_maps.read_programs(logdir)
    assert ("t-window", "f") in {(m["registry"], m["program"])
                                 for m in programs}
    assert run_report.main(["--device-trace", logdir]) == 0
    out = capsys.readouterr().out
    assert "programs" in out        # a CPU's trace has no device plane
    assert run_report.main(["--device-trace", str(tmp_path / "none")]) == 1


# ------------------------------------------- the engine's own fused step

@pytest.mark.parametrize("stage", [1, 3])
def test_the_engines_fused_step_publishes_its_op_map_on_a_mesh(stage):
    """The train engine's registry follows ``telemetry.spans.enabled``: the
    fused step of a ZeRO engine over dp=8, whose arguments are committed to
    ``NamedSharding``s, lowers again from their descriptions (shardings in
    the avals) without a further compile of the jit."""
    import deepspeed_tpu
    from tests.unit.common import (base_config, make_mesh, random_tokens,
                                   tiny_model)
    cfg = base_config(micro_batch=1, stage=stage, extra={
        "telemetry": {"enabled": True, "spans": {"enabled": True}}})
    engine, *_ = deepspeed_tpu.initialize(
        model=tiny_model(), config=cfg, mesh_manager=make_mesh(dp=8),
        rng=jax.random.PRNGKey(0))
    assert engine.compile_registry.tracer is engine.tracer
    for seed in (0, 1):
        engine.train_batch_fused(random_tokens(8, 16, seed=seed))
    assert engine.compile_registry.counts()["fused"] == 1
    fused = [m for m in op_maps.published()
             if (m["registry"], m["program"]) == ("engine", "fused")]
    assert len(fused) == 1
    scopes = {op_maps.root_scope(r["scope"]).split("/")[0]
              for r in fused[0]["ops"]}
    assert {"attention", "mlp", "optimizer", "qkv", "attn_out",
            "head", "loss"} <= scopes
    assert any("backward" in r["flags"] for r in fused[0]["ops"])
