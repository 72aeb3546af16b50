#!/usr/bin/env python3
"""Negative controls of the logits check for the window-and-full attention
family (``mellum``), at a serving cell's own sizes: the server runs with a
fault planted, the reference as it is on the weights as drawn, and the two
readings of ``compare.py`` say whether ``correct`` would turn false.

    python3 benchmarks/chip/reference/mellum_control.py \
        --workload <cell> --seed <n> [<n> ...] --fault <name> [<name> ...] \
        [--ticks <n>]

It is ``hybrid_ssm_moe_control.py`` (its process, its ``readings``) with this
family's faults.  In what the program does with its two pools, planted by
replacing a function of the program for the server's lifetime:
``window_all``: a prompt's chunk on a window layer sees every cell of its
ring and all of the chunk before it (no band); ``write_first``: the chunk is
written into its ring before it attends, so its first queries have lost the
keys it overwrote (what a ring of exactly the window forbids); ``ring_short``:
every ring an eighth of the window short (the oldest 128 keys of 1,024 are
gone: a ring sized a block of the chunk pass's keys too small);
``yarn_window``: the full layers' YaRN table on the window layers too.  In
the weights (``nemotron_h_control.py``'s two, this family's runs have its
layout: one dict per position of the unit): ``zero``: the routed product
left out; ``int8``: every matrix of every layer on 255 levels a channel.
The last line of each fault is ``CONTROL {...}``.

``UNSEEN`` names what the cell's check does NOT see at the published widths,
kept runnable so that the reading can be made again: ``ring_one_short``,
every ring ONE cell short (an off-by-one in the ring's length).  The oldest
key of 1,024 carries about a thousandth of a layer's attention on these
weights and the fault reads as a sound run (PERF.md 6); what holds the
ring's length to the cell is ``tests/unit/models/test_mellum.py`` at a
window of 16 on loud weights, where both faults read far over the tolerance.
"""

import contextlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)

from benchmarks.chip.reference import hybrid_ssm_moe_control as base  # noqa: E402
from benchmarks.chip.reference.nemotron_h_control import _each_part  # noqa: E402

def _int8(params):
    """``base._int8`` (255 levels of a matrix's largest magnitude per output
    channel) where this family stores its attention matrices head-major,
    ``[layers, heads, D, d_model]``: ``W_q``, ``W_k``, ``W_v`` put out a
    (head, element), ``W_o`` a model dimension; the rest as ``base`` has
    them, ``[..., in, out]``."""
    import jax.numpy as jnp

    def rounded(w, over):
        f = w.astype(jnp.float32)
        scale = jnp.maximum(jnp.abs(f).max(axis=over, keepdims=True) / 127.0,
                            1e-30)
        return (jnp.round(f / scale) * scale).astype(w.dtype)

    own = {"wq": -1, "wk": -1, "wv": -1, "wo": (-3, -2)}
    rest = base._int8({"runs": [{k: v for k, v in params["runs"][0].items()
                                 if k not in own}]})["runs"][0]
    return {**params, "runs": [{**rest, **{
        k: rounded(params["runs"][0][k], over) for k, over in own.items()}}]}


WEIGHTS = {"zero": _each_part(base._zero_routed), "int8": _each_part(_int8)}
#: each but ``none`` must read not ``correct`` in the cell's own check
FAULTS = ("none", "window_all", "write_first", "ring_short", "yarn_window",
          "zero", "int8")
#: planted the same way, and not seen by the cell's check (module docstring)
UNSEEN = ("ring_one_short",)


def _patches(fault: str):
    """``[(module, name, replacement)]`` of the program's functions."""
    import jax.numpy as jnp
    from deepspeed_tpu.models import gpt_inference, window_moe
    from deepspeed_tpu.ops.pallas import decode_attention as da
    ring, cached = da.ring_attention, da.cached_attention
    if fault == "window_all":
        return [(da, "ring_attention",
                 lambda q, rk, rv, fk, fv, pos, window, *a, **k: ring(
                     q, rk, rv, fk, fv, pos, 1 << 30, *a, **k))]
    if fault == "write_first":
        # the cells the chunk's write lands on are lost to its queries
        # (``k`` [B, R + chunk, ...]: the unrolled ring, then the chunk)
        return [(da, "cached_attention",
                 lambda q, k, *a, valid_from=None, **kw: cached(
                     q, k, *a, **kw, valid_from=None if valid_from is None
                     else jnp.minimum(valid_from + q.shape[1],
                                      k.shape[1] - q.shape[1])))]
    if fault in ("ring_short", "ring_one_short"):
        rule = gpt_inference.cache_ring
        short = (lambda r: max(r // 8, 1)) if fault == "ring_short" \
            else (lambda r: 1)
        return [(gpt_inference, "cache_ring", lambda config, max_len: (
            lambda r: (r[0], r[1] - short(r[1])))(rule(config, max_len)))]
    if fault == "yarn_window":
        rotate = window_moe.rotate
        return [(window_moe, "rotate", lambda x, positions, config, kind:
                 rotate(x, positions, config, window_moe.FULL))]
    return []


@contextlib.contextmanager
def planted(fault: str):
    patches = _patches(fault)
    kept = [(module, name, getattr(module, name))
            for module, name, _ in patches]
    for module, name, fn in patches:
        setattr(module, name, fn)
    try:
        yield
    finally:
        for module, name, fn in kept:
            setattr(module, name, fn)


def main(argv=None) -> int:
    # this process plants this family's faults only
    base.WEIGHTS, base.FAULTS, base.planted = WEIGHTS, FAULTS + UNSEEN, \
        planted
    return base.main(argv)


if __name__ == "__main__":
    sys.exit(main())
