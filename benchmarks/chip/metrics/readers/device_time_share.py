"""Device time of the traced slice by the program that launched an op and by
the named scope it was traced under, over the device's busy time.

The trace names an op by its optimised-HLO instruction; the program's
published op maps (``deepspeed_tpu.telemetry.op_maps``: built by each
``CompiledProgramRegistry`` whose owner's tracer is on, during warm-up) say
which program holds that instruction and under which ``jax.named_scope``.
``program`` and ``scope`` are regular expressions, searched in the program's
registered name (``admit``, ``tick``, ``fused``) and in the scope path
(``admit_chunk/mlp/norm``); a fusion that spans scopes (``attn_out+mlp/norm``)
is counted under the one that owns its root, so that shares add up.
``unjoined``: the share of busy time whose ops no program's map could place
(the guard that the join holds).

A program that publishes no op maps (the parent of the PR that added them),
or a run without a trace, has nothing to read and the metric is left out;
nothing here raises."""

import re

from ...harness import log

#: the one joined slice a run reduces, shared by the run's metrics
_JOINED = {}


def _label(o) -> str:
    return f"{o.name} {o.op} {o.shape}"[:120]


def _joined(r):
    """``(table, unjoined_s)`` of ``r.ops``, with the ``[scopes]`` line
    logged once: for each of the breakdown's ops, ``program/scope``."""
    if _JOINED.get("of") is not r:
        from deepspeed_tpu.telemetry import op_maps as dt
        maps = dt.published()
        result = None
        if maps:
            ops = [(o.device, o.name, o.op, o.shape, o.start, o.end)
                   for o in r.ops]
            hits = dt.join_ops(ops, maps)
            table, unjoined, named = {}, 0.0, {}
            for o, hit in zip(r.ops, hits):
                if hit is None:
                    unjoined += o.dur
                    continue
                key = (hit[0], dt.root_scope(hit[1]["scope"]))
                table[key] = table.get(key, 0.0) + o.dur
                named.setdefault(_label(o), "/".join(
                    (hit[0], hit[1]["scope"])) + (
                    "[" + ",".join(hit[1]["flags"]) + "]"
                    if hit[1]["flags"] else ""))
            n = len(r.devices)
            result = ({k: v / n for k, v in table.items()}, unjoined / n)
            log("scopes", programs=len(maps),
                rows=sum(len(m["ops"]) for m in maps),
                **{label.split(" ", 1)[0]: named.get(label, "unjoined")
                   for label, _ in r.top_ops})
        _JOINED.clear()
        _JOINED.update(of=r, result=result)
    return _JOINED["result"]


def read(ctx, program=None, scope=None, unjoined=False):
    r = ctx.reduced
    if r is None or r.busy_s <= 0:
        return None
    try:
        joined = _joined(r)
    except Exception as e:      # a metric is never worth a run
        log("scopes", error=repr(e)[:200].replace(" ", "_"))
        return None
    if joined is None:
        return None
    table, lost = joined
    if unjoined:
        return 100.0 * lost / r.busy_s
    took = sum(s for (p, sc), s in table.items()
               if (program is None or re.search(program, p))
               and (scope is None or re.search(scope, sc)))
    return 100.0 * took / r.busy_s
