"""Device time by program and by named scope.

A profiler's trace names a device op by its optimised-HLO instruction
(``fusion.195``); which program launched it and which ``jax.named_scope`` it
was traced under is in the compiled program's ``metadata={op_name=...}``,
which only the program holds.  This module is the table that ties them and
the one reduction that reads it:

- :func:`parse_op_map` turns a compiled program's optimised HLO text
  (``compiled.as_text()``) into its **op map**: for every instruction a
  trace can show, its name, opcode, result shape (layouts stripped: the
  three fields a trace's event gives), its scope and its flags.
- :func:`publish` keeps an op map, as plain data, in one process-wide table
  keyed by registry and program name (the newest compile wins).  The table
  outlives the registry and its owner: a reader that runs after the engine
  or the gateway is gone still finds it.  ``CompiledProgramRegistry``
  (``utils/compile_watch.py``) is what builds and publishes: right after a
  compile when its owner's tracer is enabled, or on demand
  (``publish_op_maps``).
- :func:`device_time` joins a trace's device ops to the published table and
  returns seconds by ``(program, scope)`` and the seconds it could not
  place (``unjoined``: never guessed).

**Scope.**  The ``jax.named_scope`` path of an instruction's ``op_name``
(``mlp/norm``), with what the transformations wrap around it kept apart as
flags: ``jit(...)``, ``jvp()``, ``while/body``, ``closed_call``,
``checkpoint`` are dropped from the name, ``transpose(...)`` sets
``backward`` and ``rematted_computation`` sets ``remat``; the primitive
(the last component) and an einsum's specification are dropped too.  A
fusion takes the scopes of the instructions of its fused computation: one
scope if they agree, else all of them joined by ``+`` with the one that
owns its root first (``attn_out+mlp/norm``: nothing is picked).  An
instruction the compiler made with no scope of its own (the layer scan's
stacking ``dynamic-update-slice``, a copy, a bitcast) takes the scope of
the instruction that produced its operand, flagged ``stacked`` (an update
slice), ``copied`` (a copy, slice or re-layout) or ``inherited`` (anything
else).

**Join.**  The key is ``(name, opcode, shape)``.  Where two programs hold
the same key the compute stream decides: it runs one program at a time, so
an ambiguous op takes the program of its nearest unambiguous neighbours on
the same device, if that program holds the key.  What no program holds, or
what lies between two programs that both do, is ``unjoined``.
"""

from __future__ import annotations

import functools
import json
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = ["parse_op_map", "scope_of", "publish", "published",
           "clear_published", "join_ops", "device_time", "format_report",
           "write_programs", "read_programs", "read_trace_ops",
           "PROGRAMS_FILE", "NO_SCOPE"]

#: beside the ``.xplane.pb`` of an operator's capture window
PROGRAMS_FILE = "programs.json"
#: the scope of an instruction that was traced under no named scope
NO_SCOPE = "-"

# ``%name = shape opcode(``; the shape may be a tuple.  The same expression
# the chip benchmark reads a trace's events with
# (``benchmarks/chip/trace/reduce.py``): both sides of the join must agree
# on an instruction's three fields.
_HLO = re.compile(r"^%(?P<name>\S+) = (?P<shape>\(.*?\)|\S+) "
                  r"(?P<op>[a-z][a-z0-9-]*)\(")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) \(.*\{\s*$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLED = re.compile(r"(calls|body|condition|to_apply|true_computation|"
                     r"false_computation)=%([^\s,)}]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_OPERAND = re.compile(r"%([^\s,(){}]+)")
_TRANSFORM = re.compile(r"^([A-Za-z_][\w.]*)\((.*)\)$")

#: components of an ``op_name`` that a transformation or a control-flow
#: primitive puts there, not a ``jax.named_scope``
_WRAPPERS = frozenset((
    "while", "body", "cond", "body_fun", "cond_fun", "closed_call",
    "core_call", "core_closed_call", "checkpoint", "remat", "remat2",
    "rematted_computation", "custom_jvp_call", "custom_vjp_call",
    "custom_vjp_call_jaxpr", "custom_lin", "shard_map", "xla_call", "scan",
    "pallas_call"))
_BRANCH = re.compile(r"^branch_\d+_fun$")
#: transformations whose argument is a function's name, not a scope
_NAMES_A_FUNCTION = frozenset(("jit", "pjit", "xla_call", "named_call"))
#: instructions no trace shows as an event of their own
_NOT_EVENTS = frozenset(("parameter", "constant", "get-tuple-element",
                         "tuple"))
_STACKING = ("dynamic-update-slice",)
#: (an asynchronous copy or slice is ``async-start`` / ``async-done`` here)
_COPYING = frozenset((
    "copy", "bitcast", "bitcast-convert", "transpose", "reshape", "slice",
    "dynamic-slice", "concatenate", "pad", "broadcast", "convert",
    "async-start", "async-done"))


def _strip_layout(shape: str) -> str:
    return re.sub(r"\{[^}]*\}", "", shape)


def _norm_opcode(opcode: str) -> str:
    """A trace prints an asynchronous pair as ``async-start`` /
    ``async-done`` where the program's text prints ``slice-start`` /
    ``slice-done``: both ends of the join see one spelling."""
    if opcode.endswith("-start"):
        return "async-start"
    if opcode.endswith("-done"):
        return "async-done"
    return opcode


def op_key(name: str, opcode: str, shape: str) -> Tuple[str, str, str]:
    return name, _norm_opcode(opcode), shape


# ---------------------------------------------------------------- scopes

def _split(path: str, sep: str = "/") -> List[str]:
    """``path`` cut at ``sep`` outside parentheses."""
    out, depth, at = [], 0, 0
    for i, c in enumerate(path):
        if c == "(":
            depth += 1
        elif c == ")":
            depth = max(0, depth - 1)
        elif c == sep and depth == 0:
            out.append(path[at:i])
            at = i + 1
    out.append(path[at:])
    return out


_LOOP = frozenset(("while", "body", "cond", "body_fun", "cond_fun"))


def _component(part: str, scopes: List[str], flags: set) -> bool:
    """One component of an ``op_name``: a wrapper (dropped, perhaps a
    flag), a transformation around a scope (``jvp(loss)``: the scope is
    ``loss``), or a named scope.  True if it added a scope."""
    m = _TRANSFORM.match(part)
    if m:
        transform, inner = m.group(1), m.group(2)
        if transform == "transpose":
            flags.add("backward")
        if transform in _NAMES_A_FUNCTION or not inner:
            return False
        return any([_component(p, scopes, flags) for p in _split(inner)])
    if part == "rematted_computation":
        flags.add("remat")
    # ``bsd,df->bsf``: an einsum names its own call; ``f.<locals>.g``: a
    # function's qualified name, put there by a primitive's lowering
    if not part or part in _WRAPPERS or _BRANCH.match(part) \
            or "->" in part or "<" in part:
        return False
    scopes.append(part)
    return True


@functools.lru_cache(maxsize=4096)      # a program repeats few names often
def _scope(op_name: str) -> Tuple[str, Tuple[str, ...], bool]:
    """``(scope path, flags, machinery)``: ``machinery`` where a loop's
    wrapper follows the last named scope (``mlp/while/body/dynamic_slice``:
    the loop's own slicing and stacking, inside ``mlp`` and of no scope
    below it)."""
    flags: set = set()
    found, machinery = "", False
    for n, one in enumerate(op_name.split(";")):
        scopes: List[str] = []
        loop_after = False
        for part in _split(one)[:-1]:        # the last is the primitive
            if _component(part, scopes, flags):
                loop_after = False
            elif part in _LOOP:
                loop_after = True
        if not found and (scopes or n == 0):
            found, machinery = "/".join(scopes), loop_after
    return found, tuple(sorted(flags)), machinery


def scope_of(op_name: str) -> Tuple[str, Tuple[str, ...]]:
    """``(scope path, flags)`` of one ``op_name``; the path is ``""`` where
    no named scope encloses the instruction.  Merged metadata
    (``a/b/mul;c/add``) gives the first of its names that has a scope."""
    return _scope(op_name)[:2]


# ---------------------------------------------------------------- parsing

class _Instr:
    __slots__ = ("name", "opcode", "shape", "op_name", "operands", "called",
                 "root")

    def __init__(self, name, opcode, shape, op_name, operands, called, root):
        self.name, self.opcode, self.shape = name, opcode, shape
        self.op_name, self.operands, self.called = op_name, operands, called
        self.root = root


def _arguments(line: str, start: int) -> str:
    """The text between the parenthesis at ``start - 1`` and its match."""
    depth = 1
    for i in range(start, len(line)):
        c = line[i]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                return line[start:i]
    return line[start:]


def _computations(hlo_text: str) -> Dict[str, List[_Instr]]:
    comps: Dict[str, List[_Instr]] = {}
    current: Optional[List[_Instr]] = None
    for raw in hlo_text.splitlines():
        line = raw.strip()
        if current is None:
            head = _COMPUTATION.match(line)
            if head:
                current = comps.setdefault(head.group(1), [])
            continue
        if line == "}":
            current = None
            continue
        root = line.startswith("ROOT ")
        if root:
            line = line[5:]
        m = _HLO.match(line)
        if not m:
            continue
        args = _arguments(line, m.end())
        rest = line[m.end() + len(args):]
        named = _OP_NAME.search(rest)
        called = [c for _, c in _CALLED.findall(rest)]
        branches = _BRANCHES.search(rest)
        if branches:
            called += _OPERAND.findall(branches.group(1))
        current.append(_Instr(
            m["name"], m["op"], _strip_layout(m["shape"]),
            named.group(1) if named else "", _OPERAND.findall(args), called,
            root))
    return comps


def _inherit(instr: _Instr, by_name: Dict[str, _Instr], scoped,
             limit: int = 64) -> Tuple[str, Tuple[str, ...]]:
    """The scope of the nearest instruction that produced an operand of
    ``instr`` and has one (breadth first, the operands in order)."""
    seen, queue = {instr.name}, list(instr.operands)
    while queue and limit > 0:
        name = queue.pop(0)
        limit -= 1
        if name in seen or name not in by_name:
            continue
        seen.add(name)
        got = scoped(by_name[name])
        if got[0]:
            return got
        queue += by_name[name].operands
    return "", ()


def _elements(shape: str) -> int:
    """Elements of the (first) array of a result shape."""
    m = re.search(r"\[([\d,]*)\]", shape)
    n = 1
    for d in (m.group(1).split(",") if m and m.group(1) else ()):
        n *= int(d)
    return n


def _how_inherited(name: str, opcode: str) -> str:
    """``opcode``: the instruction's, or the root's of its fusion (whose
    name the compiler makes of its opcodes:
    ``bitcast_dynamic-update-slice_fusion``)."""
    if opcode in _STACKING or any(s in name for s in _STACKING):
        return "stacked"
    return "copied" if _norm_opcode(opcode) in _COPYING else "inherited"


def parse_op_map(hlo_text: str) -> List[dict]:
    """The op map of one compiled program: a row ``{"name", "opcode",
    "shape", "scope", "flags"}`` for every instruction of every computation
    that runs as itself (the entry, loop bodies and conditions, branches,
    called computations).  The instructions of a fused computation are read
    for their fusion's scope and are no rows: a trace shows the fusion."""
    comps = _computations(hlo_text)
    fused = {c for instrs in comps.values() for i in instrs
             if i.opcode == "fusion" for c in i.called}
    applied = {c for instrs in comps.values() for i in instrs
               if i.opcode != "fusion" and not i.opcode.startswith("async")
               and i.opcode not in ("while", "conditional", "call")
               for c in i.called}       # a reduce's or a scatter's scalar fn
    memo: Dict[Tuple[str, str], Tuple[str, Tuple[str, ...], str]] = {}

    def fusion_scope(instr: _Instr):
        """Scopes of the fused computation's instructions, the root's first
        (of several results, the largest's); flags of all of them; the
        root's opcode.  A loop's own slicing inside a scope names the
        fusion only where nothing else does."""
        body = [i for c in instr.called for i in comps.get(c, ())
                if i.opcode != "parameter"]
        names = {i.name: i for i in body}
        # what names a fusion: its instructions' scopes, less the loop's own
        # slicing (unless that is all there is)
        real = {i.name for i in body if i.op_name and not _scope(i.op_name)[2]}
        own = lambda i: _scope(i.op_name)[:2] \
            if not real or i.name in real else ("", ())
        root = next((i for i in body if i.root), None)
        outs = [root] if root is not None else []
        if root is not None and root.opcode == "tuple":
            outs = sorted((names[o] for o in root.operands if o in names),
                          key=lambda i: -_elements(i.shape))
        first = ""
        for out in outs:
            first = own(out)[0] or _inherit(out, names, own)[0]
            if first:
                break
        scopes, flags = [first] if first else [], set()
        for i in body:
            s, f = own(i)
            if s:
                flags.update(f)
                if s not in scopes:
                    scopes.append(s)
        if not scopes:      # the fusion's own name is all there is
            s, f = _scope(instr.op_name)[:2]
            scopes, flags = [s] if s else [], set(f)
        elif outs and outs[0].opcode in _STACKING \
                and outs[0].name not in real:
            # a loop's own update slice around a scoped value (a bitcast
            # carries its producer's name): the loop stacks that value
            flags.add("stacked")
        return ("+".join(scopes), tuple(sorted(flags)),
                outs[0].opcode if outs else "")

    def scoped_in(comp: str):
        by_name = {i.name: i for i in comps[comp]}

        def own(instr: _Instr):
            key = (comp, instr.name)
            if key not in memo:
                if instr.opcode in ("parameter", "constant"):
                    memo[key] = ("", (), instr.opcode)
                elif instr.opcode == "fusion":
                    memo[key] = fusion_scope(instr)
                else:
                    memo[key] = scope_of(instr.op_name) + (instr.opcode,)
            return memo[key][:2]

        def resolve(instr: _Instr):
            scope, flags = own(instr)
            if scope:
                return scope, flags
            scope, inherited = _inherit(instr, by_name, own)
            if not scope:
                return NO_SCOPE, flags
            # its own flags (a stacking update slice of the backward scan
            # is ``backward``) beside how it came by its scope
            how = _how_inherited(instr.name, memo[(comp, instr.name)][2])
            return scope, tuple(sorted(set(flags) | {how}))
        return resolve

    rows = []
    for comp, instrs in comps.items():
        if comp in fused or comp in applied:
            continue
        resolve = scoped_in(comp)
        for instr in instrs:
            if instr.opcode in _NOT_EVENTS:
                continue
            scope, flags = resolve(instr)
            rows.append({"name": instr.name, "opcode": instr.opcode,
                         "shape": instr.shape, "scope": scope,
                         "flags": list(flags)})
    return rows


# ------------------------------------------------------- published table

#: (registry name, program name) -> {"registry", "program", "ops": rows}.
#: Plain data: strings and lists, no jit object, no array.
_PUBLISHED: Dict[Tuple[str, str], dict] = {}


def publish(registry: str, program: str, rows: List[dict]) -> None:
    """Keep ``rows`` as the op map of ``program`` of ``registry``,
    replacing an older compile's."""
    _PUBLISHED[(registry, program)] = {
        "registry": registry, "program": program, "ops": rows}


def published() -> List[dict]:
    """The published op maps, oldest first."""
    return list(_PUBLISHED.values())


def clear_published() -> None:
    _PUBLISHED.clear()


def write_programs(logdir: str) -> str:
    """The published table as ``programs.json`` under ``logdir``."""
    path = os.path.join(logdir, PROGRAMS_FILE)
    with open(path, "w") as f:
        json.dump({"programs": published()}, f)
    return path


def read_programs(logdir: str) -> List[dict]:
    with open(os.path.join(logdir, PROGRAMS_FILE)) as f:
        return json.load(f)["programs"]


# ------------------------------------------------------------------ join

Op = Tuple[int, str, str, str, float, float]   # device, name, opcode, shape,
#                                                start, end (seconds)


def join_ops(ops: Sequence[Op], op_maps: Optional[List[dict]] = None
             ) -> List[Optional[Tuple[str, dict]]]:
    """For each op of ``ops``, ``(program, row)`` of the op map that holds
    it, or None where it cannot be placed (the module's docstring gives the
    rule).  ``op_maps``: the published table by default."""
    maps = published() if op_maps is None else op_maps
    holders: Dict[Tuple[str, str, str], List[Tuple[int, dict]]] = {}
    for p, m in enumerate(maps):
        for row in m["ops"]:
            holders.setdefault(
                op_key(row["name"], row["opcode"], row["shape"]),
                []).append((p, row))
    cands = [holders.get(op_key(o[1], o[2], o[3]), ()) for o in ops]
    placed: List[Optional[int]] = [
        c[0][0] if len({p for p, _ in c}) == 1 else None for c in cands]
    by_device: Dict[int, List[int]] = {}
    for i, o in enumerate(ops):
        by_device.setdefault(o[0], []).append(i)
    for order in by_device.values():
        order.sort(key=lambda i: ops[i][4])
        sure = [placed[i] for i in order]
        before, last = [], None
        for p in sure:
            before.append(last)
            last = p if p is not None else last
        after, nxt = [None] * len(order), None
        for k in range(len(order) - 1, -1, -1):
            after[k] = nxt
            nxt = sure[k] if sure[k] is not None else nxt
        for k, i in enumerate(order):
            if placed[i] is not None or not cands[i]:
                continue
            mine = {p for p, _ in cands[i]}
            around = {p for p in (before[k], after[k]) if p in mine}
            if len(around) == 1:
                placed[i] = around.pop()
    out: List[Optional[Tuple[str, dict]]] = []
    for i, p in enumerate(placed):
        if p is None:
            out.append(None)
        else:
            row = next(r for q, r in cands[i] if q == p)
            out.append((maps[p]["program"], row))
    return out


def device_time(ops: Sequence[Op], op_maps: Optional[List[dict]] = None
                ) -> Tuple[Dict[Tuple[str, str], float], float]:
    """Seconds by ``(program, scope)`` and the seconds left ``unjoined``,
    both the mean over the devices ``ops`` name.  ``ops``: plain ``(device,
    name, opcode, shape, start, end)`` tuples of a trace's compute
    stream."""
    devices = len({o[0] for o in ops}) or 1
    table: Dict[Tuple[str, str], float] = {}
    unjoined = 0.0
    for o, hit in zip(ops, join_ops(ops, op_maps)):
        dur = o[5] - o[4]
        if hit is None:
            unjoined += dur
        else:
            key = (hit[0], hit[1]["scope"])
            table[key] = table.get(key, 0.0) + dur
    return ({k: v / devices for k, v in table.items()}, unjoined / devices)


def root_scope(scope: str) -> str:
    """Of a fusion's ``a+b``, the scope that owns its root: what a share by
    scope counts the fusion under, so that shares add up."""
    return scope.split("+", 1)[0]


def format_report(ops: Sequence[Op], op_maps: Optional[List[dict]] = None,
                  top: int = 12) -> List[str]:
    """Lines for ``run_report.py --device-trace``: device seconds by
    program and, inside each, by scope, with the unjoined share."""
    table, unjoined = device_time(ops, op_maps)
    busy = sum(table.values()) + unjoined
    if busy <= 0:
        return ["device time: the trace holds no device operation"]
    share = lambda s: f"{s:9.6f} s {100.0 * s / busy:6.2f}%"
    programs: Dict[str, Dict[str, float]] = {}
    for (program, scope), s in table.items():
        programs.setdefault(program, {})[scope] = s
    lines = [f"device time by program and scope (mean over devices; "
             f"{busy:.6f} s in ops)"]
    for program, scopes in sorted(programs.items(),
                                  key=lambda kv: -sum(kv[1].values())):
        lines.append(f"  {program:<28}{share(sum(scopes.values()))}")
        ranked = sorted(scopes.items(), key=lambda kv: -kv[1])
        for scope, s in ranked[:top]:
            lines.append(f"    {scope:<26}{share(s)}")
        if len(ranked) > top:
            lines.append(f"    {'(' + str(len(ranked) - top) + ' more)':<26}"
                         f"{share(sum(s for _, s in ranked[top:]))}")
    lines.append(f"  {'unjoined':<28}{share(unjoined)}")
    return lines


# ------------------------------------------------------------- the trace

_DEVICE_PLANE = re.compile(r"^/device:\w+:(\d+)$")
#: a loop or a call is one event around the events of its body
_WRAPPER_OPS = ("while", "conditional", "call")


def read_trace_ops(logdir: str) -> List[Op]:
    """The compute stream's ops (line ``XLA Ops`` of every device plane) of
    the newest ``.xplane.pb`` under ``logdir``, as :func:`device_time`
    takes them."""
    import glob

    from jax.profiler import ProfileData
    found = sorted(glob.glob(os.path.join(
        logdir, "**", "*.xplane.pb"), recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    ops: List[Op] = []
    for plane in ProfileData.from_file(found[-1]).planes:
        m = _DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                hlo = _HLO.match(e.name)
                if not hlo or hlo["op"] in _WRAPPER_OPS:
                    continue
                ops.append((int(m[1]), hlo["name"], hlo["op"],
                            _strip_layout(hlo["shape"]), e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9))
    return ops
