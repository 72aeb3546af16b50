"""Operations and bytes the algorithms need, computed from shapes.

Recomputed operations never count: these are what the mathematics requires,
so a share of peak built on them cannot pass 100%.
"""

from __future__ import annotations

import re
from typing import List, Tuple

_SHAPE = re.compile(r"(bf16|f16|f32|s32|s8|u8)\[([\d,]*)\]")
_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "s8": 1, "u8": 1}


def train_flops_per_token(file_sizes: dict, seq_len: int,
                          padded_vocab: int) -> float:
    """Forward and backward of a dense decoder per trained token: 6 per
    parameter that takes part in a matrix product, plus attention's
    ``12 L d S`` (scores and values, forward and backward, not halved for
    causality: the convention of ``models/gpt.py::flops_per_token`` and of
    the MFU figures quoted for this repo since August)."""
    d, L = file_sizes["d_model"], file_sizes["n_layer"]
    d_ff = file_sizes["d_ff"]
    per_layer = 4 * d * d + 4 * d + 2 * d * d_ff + d + d_ff + 4 * d
    n = padded_vocab * d + seq_len * d + L * per_layer + 2 * d
    return 6.0 * n + 12.0 * L * d * seq_len


def parse_shapes(shape: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """``(dtype, dims)`` of every array in an HLO result shape."""
    return [(t, tuple(int(x) for x in dims.split(",") if x))
            for t, dims in _SHAPE.findall(shape)]


def flash_call(shape: str, causal: bool = True) -> Tuple[float, float]:
    """``(operations, bytes)`` one flash-attention custom call needs, told
    from its result shape (``ops/pallas/flash_attention.py``):

    - forward returns ``(o bf16[BH,S,D], lse f32[BH,1,S])``: ``q k^T`` and
      ``p v``, ``4 BH S^2 D`` operations, halved under a causal mask; reads
      q, k, v and writes o and lse;
    - a backward pass returns ``dk, dv`` (and, fused, the partial ``dq``):
      five products (scores again, ``dp``, ``dv``, ``dk``, ``dq``),
      ``10 BH S^2 D`` halved; reads q, k, v, do, lse, delta and writes dq,
      dk, dv.  Where the backward is two kernels, the one that returns only
      ``dq`` adds time and no further required operation.
    """
    arrays = parse_shapes(shape)
    three = [dims for t, dims in arrays if len(dims) == 3 and t == "bf16"]
    if not three:
        return 0.0, 0.0
    BH, S, D = three[0]
    half = 0.5 if causal else 1.0
    is_fwd = any(t == "f32" and dims == (BH, 1, S) for t, dims in arrays)
    if is_fwd:
        return 4.0 * BH * S * S * D * half, 4 * BH * S * D * 2 + 4 * BH * S
    if len(three) >= 2:          # dk and dv: one whole backward pass
        return 10.0 * BH * S * S * D * half, 7 * BH * S * D * 2 + 8 * BH * S
    return 0.0, 0.0              # dq alone: counted with its pass


def decode_call(rows_context_tokens: float, n_head: int, head_dim: int,
                kv_bytes: int = 2) -> Tuple[float, float]:
    """``(operations, bytes)`` of one decode-attention call over a batch
    whose rows hold ``rows_context_tokens`` cached tokens in all: one query
    row against every cached key and value (``4 H D`` operations and
    ``2 H D`` cache elements read per cached token)."""
    n = rows_context_tokens * n_head * head_dim
    return 4.0 * n, 2.0 * n * kv_bytes


def decode_call_dense(config, rows_context_tokens: float
                      ) -> Tuple[float, float]:
    """``decode_call`` of a model whose cache row is ``2 H D`` elements a
    token in the type it is served in (two bytes): the default counting
    function of the ``decode_roofline`` reader.  A family whose cache row is
    another (a latent row, a window, a state) brings a function of this
    signature, ``f(model config, cached tokens) -> (operations, bytes)``,
    in a module of its own and names it in its metric file's ``args``."""
    return decode_call(rows_context_tokens, config.n_head, config.head_dim)
