"""Shared plumbing for the per-op collective benchmarks.

Counterpart of the reference's ``benchmarks/communication/utils.py``
(argument surface: --trials/--warmups/--maxsize/--bw-unit/--scan/--raw/
--dtype/--mem-size) rebuilt for the XLA collective path: ops run inside
``shard_map`` over the global mesh's flattened axis, so on hardware they
lower to the same ICI collectives training issues.
"""

from __future__ import annotations

import argparse
import re
import time
from typing import Callable, Dict, List

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ...utils.comms_logging import get_bw

AXIS = "bench"

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
          "float16": jnp.float16, "int8": jnp.int8}


def benchmark_parser() -> argparse.ArgumentParser:
    """The reference's shared benchmark arg surface (utils.py)."""
    p = argparse.ArgumentParser(description="deepspeed_tpu comm benchmark")
    p.add_argument("--trials", type=int, default=20,
                   help="timed iterations per size")
    p.add_argument("--warmups", type=int, default=5,
                   help="untimed iterations per size (first one compiles)")
    p.add_argument("--minsize", type=int, default=1 << 16,
                   help="scan-mode smallest message, bytes")
    p.add_argument("--maxsize", type=int, default=1 << 26,
                   help="scan-mode largest message, bytes")
    p.add_argument("--step-factor", type=int, default=4,
                   help="scan-mode multiplicative size step")
    p.add_argument("--scan", action="store_true",
                   help="sweep the size ladder; default is single size")
    p.add_argument("--elements", type=int, default=None,
                   help="single-run element count (overrides --mem-size)")
    p.add_argument("--mem-size", default="64MB",
                   help="single-run message size, e.g. 512KB / 64MB / 1GB")
    p.add_argument("--dtype", default="bfloat16", choices=sorted(DTYPES))
    p.add_argument("--bw-unit", default="Gbps", choices=["Gbps", "GBps"])
    p.add_argument("--raw", action="store_true",
                   help="print one csv row per measurement, no table")
    return p


def parse_mem_size(text: str) -> int:
    m = re.fullmatch(r"\s*(\d+(?:\.\d+)?)\s*([KMG]?)i?B?\s*", text,
                     re.IGNORECASE)
    if not m:
        raise ValueError(f"bad --mem-size {text!r} (want e.g. 64MB)")
    mult = {"": 1, "K": 1 << 10, "M": 1 << 20, "G": 1 << 30}[
        m.group(2).upper()]
    return int(float(m.group(1)) * mult)


def sizes_from_args(args) -> List[int]:
    if args.scan:
        sizes, s = [], args.minsize
        while s <= args.maxsize:
            sizes.append(s)
            s *= max(args.step_factor, 2)
        return sizes
    if args.elements is not None:
        return [args.elements * np.dtype(
            jnp.zeros((), DTYPES[args.dtype]).dtype).itemsize]
    return [parse_mem_size(args.mem_size)]


def bench_mesh() -> Mesh:
    return Mesh(np.array(jax.devices()), (AXIS,))


def timed(fn: Callable, x, trials: int, warmups: int) -> float:
    out = None
    for _ in range(max(warmups, 1)):  # at least once: compile outside timing
        out = fn(x)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(trials):
        out = fn(x)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / trials


def measure(op: str, fn: Callable, sizes_bytes: List[int], dtype,
            trials: int, warmups: int, n: int) -> List[Dict]:
    itemsize = jnp.zeros((), dtype).dtype.itemsize
    results = []
    for size in sizes_bytes:
        elems = max(n, size // itemsize)
        elems = (elems // n) * n  # divisible for sharding
        x = jnp.ones((elems,), dtype)
        dt = timed(fn, x, trials, warmups)
        msg_bytes = elems * itemsize
        algbw, busbw = get_bw("ppermute" if op == "pt2pt" else op,
                              msg_bytes, dt, n)
        results.append({"op": op, "bytes": msg_bytes,
                        "latency_us": dt * 1e6,
                        "algbw_gbps": algbw, "busbw_gbps": busbw})
    return results


def _fmt_bw(gbps: float, unit: str) -> float:
    return gbps / 8.0 if unit == "GBps" else gbps


def print_results(results: List[Dict], args) -> None:
    u = args.bw_unit
    if args.raw:
        print(f"op,bytes,latency_us,algbw_{u},busbw_{u}")
        for r in results:
            print(f"{r['op']},{r['bytes']},{r['latency_us']:.2f},"
                  f"{_fmt_bw(r['algbw_gbps'], u):.4f},"
                  f"{_fmt_bw(r['busbw_gbps'], u):.4f}")
        return
    print(f"{'op':16} {'size':>14} {'latency(us)':>12} "
          f"{'algbw(' + u + ')':>13} {'busbw(' + u + ')':>13}")
    for r in results:
        print(f"{r['op']:16} {r['bytes']:>14,} {r['latency_us']:>12.1f} "
              f"{_fmt_bw(r['algbw_gbps'], u):>13.2f} "
              f"{_fmt_bw(r['busbw_gbps'], u):>13.2f}")


def run_from_args(op: str, args) -> List[Dict]:
    """Build + run one op per the parsed args; shared by per-op mains."""
    from .run_all import build_op
    mesh = bench_mesh()
    fn = build_op(op, mesh)
    results = measure(op, fn, sizes_from_args(args), DTYPES[args.dtype],
                      args.trials, args.warmups, mesh.devices.size)
    return results


def per_op_main(op: str, argv=None) -> int:
    args = benchmark_parser().parse_args(argv)
    print(f"devices: {len(jax.devices())} ({jax.default_backend()})")
    print_results(run_from_args(op, args), args)
    return 0
