"""MPMD pipeline: per-stage compiled programs, host-driven 1F1B.

``spmd.py`` compiles the whole 1F1B schedule into ONE program over the
``pipe`` mesh axis — perfect on a single slice, fatal across slices: a
single preemption anywhere kills the job, and the program can never span
a DCN boundary.  This module splits the same pipeline into *stage groups*,
each running its OWN compiled program in its own OS process, with boundary
activations/grads streamed between them (framed, SHA-256-verified
transport with a spool-file fallback) and the schedule walked by the host
runtime tick by tick.

Bitwise parity with the SPMD engine is a hard contract (the goodput
harness judges faulted continuations against unfaulted runs byte for
byte), so the per-stage programs mirror the SPMD jaxpr *structurally*:

- the stage index is a **traced** ``int32`` argument, so ``is_first`` /
  ``is_last`` are traced booleans and both ``lax.cond`` branches compile
  exactly as they do inside the shard_map body (one compiled program
  serves every stage — zero steady-state recompiles, and a respawned
  stage reuses the cache entry its predecessor warmed);
- the microbatch is picked with ``lax.dynamic_index_in_dim`` over the
  full micro stack, exactly as the SPMD tick body does;
- gradient accumulation is fused INTO the backward program (accumulators
  are passed in and returned), matching the SPMD carry;
- the loss/denominator epilogue (``max(denom, 1)``, ``loss/denom``,
  ``grads × 1/denom``) is the SPMD epilogue verbatim, with the psum
  replaced by a stage-ordered host-side sum (bitwise-equal for two
  stages; matches psum's linear reduction order in general).

The schedule itself comes from :func:`spmd.schedule_tables` — one source
of truth for both executors.
"""

from __future__ import annotations

import hashlib
import json
import os
import time
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ...telemetry.spans import SpanName
from .spmd import schedule_tables

PyTree = Any
f32 = jnp.float32

#: boundary-exchange message kinds riding the ``activation`` flow
EXCHANGE_KINDS = ("act", "grad", "part", "total")


class QuiesceSignal(Exception):
    """Raised out of a blocking exchange receive (or checked at step
    boundaries) when the fleet epoch advanced: a peer stage died and the
    supervisor ordered the group to quiesce, consensus-resume and replay.
    """

    def __init__(self, epoch: int):
        super().__init__(f"fleet epoch advanced to {epoch}")
        self.epoch = int(epoch)


class ExchangeTimeout(Exception):
    """A boundary receive outlived its deadline with no epoch bump — the
    caller escalates (the supervisor will see the stalled heartbeat)."""


# --------------------------------------------------------------------------
# leaf codec: a PyTree of arrays <-> (meta, blob) for the activation flow


def pack_tree(tree: PyTree) -> Tuple[List[Dict[str, Any]], bytes]:
    """Serialize a tree's leaves (flatten order) to raw bytes + metadata.

    The receiver owns the treedef (it has a template of what it expects),
    so only shapes/dtypes travel — no pickled structure on the wire.
    """
    leaves = jax.tree_util.tree_leaves(tree)
    arrs = [np.asarray(jax.device_get(x)) for x in leaves]
    blob = b"".join(a.tobytes() for a in arrs)
    meta = [{"shape": list(a.shape), "dtype": str(a.dtype)} for a in arrs]
    return meta, blob


def unpack_tree(template: PyTree, meta: List[Dict[str, Any]],
                blob: bytes) -> PyTree:
    """Rebuild a tree from :func:`pack_tree` output using the receiver's
    own ``template`` treedef (leaves may be ShapeDtypeStructs)."""
    flat, treedef = jax.tree_util.tree_flatten(template)
    if len(flat) != len(meta):
        raise ValueError(
            f"exchange arity mismatch: template has {len(flat)} leaves, "
            f"frame carries {len(meta)}")
    out: List[jnp.ndarray] = []
    off = 0
    for m in meta:
        dt = np.dtype(m["dtype"])
        shape = tuple(int(d) for d in m["shape"])
        count = int(np.prod(shape)) if shape else 1
        a = np.frombuffer(blob, dtype=dt, count=count, offset=off)
        off += a.nbytes
        out.append(jnp.asarray(a.reshape(shape)))
    return jax.tree_util.tree_unflatten(treedef, out)


# --------------------------------------------------------------------------
# exchanges


class LoopbackExchange:
    """In-process exchange for tests and the local (single-process) MPMD
    runner: one shared dict, keyed exactly like the wire protocol, with
    every payload round-tripped through :func:`pack_tree` so the codec is
    on the parity-critical path even without sockets."""

    def __init__(self):
        self._store: Dict[Tuple, Tuple[List[Dict[str, Any]], bytes]] = {}
        self.bytes_moved = 0

    def send(self, kind: str, epoch: int, step: int, micro: int,
             src: int, dst: int, tree: PyTree) -> None:
        meta, blob = pack_tree(tree)
        self.bytes_moved += len(blob)
        self._store[(dst, kind, epoch, step, micro, src)] = (meta, blob)

    def recv(self, kind: str, epoch: int, step: int, micro: int,
             src: int, dst: int, template: PyTree) -> PyTree:
        key = (dst, kind, epoch, step, micro, src)
        try:
            meta, blob = self._store.pop(key)
        except KeyError:
            raise ExchangeTimeout(f"loopback: nothing pending for {key}")
        return unpack_tree(template, meta, blob)

    def check_epoch(self, epoch: int) -> None:  # loopback never quiesces
        return None


class TransportExchange:
    """Boundary exchange over the framed fleet transport (``activation``
    flow) with a spool-file fallback: a degraded link slows training, it
    never corrupts it (both carriers are SHA-256-verified end to end).

    ``epoch_fn`` is polled inside blocking receives; when it reports an
    epoch newer than the step's, :class:`QuiesceSignal` is raised so the
    stage abandons the in-flight step at the microbatch barrier and
    rejoins the group's consensus resume.
    """

    def __init__(self, transport, run_dir: str, stage: int,
                 epoch_fn: Optional[Callable[[], int]] = None,
                 deadline_s: float = 30.0, tracer=None):
        self.transport = transport
        self.run_dir = str(run_dir)
        self.stage = int(stage)
        self.epoch_fn = epoch_fn
        self.deadline_s = float(deadline_s)
        self.tracer = tracer
        self.spool_sends = 0
        self.spool_recvs = 0
        self._pending: Dict[Tuple, Tuple[List[Dict[str, Any]], bytes]] = {}
        os.makedirs(self._spool_dir(self.stage), exist_ok=True)

    # -- spool fallback ---------------------------------------------------
    def _spool_dir(self, dst: int) -> str:
        return os.path.join(self.run_dir, "spool", "act", f"to{dst}")

    @staticmethod
    def _spool_name(kind: str, epoch: int, step: int, micro: int,
                    src: int) -> str:
        return f"{kind}.e{epoch}.s{step}.m{micro}.f{src}"

    def _spool_write(self, kind: str, epoch: int, step: int, micro: int,
                     src: int, dst: int, meta, blob: bytes,
                     sha256: str) -> None:
        d = self._spool_dir(dst)
        os.makedirs(d, exist_ok=True)
        base = os.path.join(d, self._spool_name(kind, epoch, step, micro,
                                                src))
        tmp = base + ".bin.tmp"
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, base + ".bin")
        tmp = base + ".json.tmp"
        with open(tmp, "w") as f:
            json.dump({"meta": meta, "sha256": sha256}, f)
        # the sidecar lands last: its presence certifies the blob is whole
        os.replace(tmp, base + ".json")
        self.spool_sends += 1

    def _spool_read(self, kind: str, epoch: int, step: int, micro: int,
                    src: int) -> Optional[Tuple[List[Dict[str, Any]],
                                                bytes]]:
        base = os.path.join(self._spool_dir(self.stage),
                            self._spool_name(kind, epoch, step, micro, src))
        if not os.path.exists(base + ".json"):
            return None
        try:
            with open(base + ".json") as f:
                side = json.load(f)
            with open(base + ".bin", "rb") as f:
                blob = f.read()
        except (OSError, ValueError):
            return None
        if hashlib.sha256(blob).hexdigest() != side.get("sha256"):
            return None  # torn spool file: keep waiting for a good copy
        self.spool_recvs += 1
        return side["meta"], blob

    # -- protocol ---------------------------------------------------------
    def send(self, kind: str, epoch: int, step: int, micro: int,
             src: int, dst: int, tree: PyTree) -> None:
        meta, blob = pack_tree(tree)
        sha = hashlib.sha256(blob).hexdigest()
        header = {"kind": kind, "epoch": int(epoch), "step": int(step),
                  "micro": int(micro), "src": int(src), "dst": int(dst),
                  "meta": meta, "sha256": sha}
        ok = self.transport.send("activation", "stage", dst, header, blob)
        if not ok:
            # breaker open or retry budget spent: the spool carries it
            self._spool_write(kind, epoch, step, micro, src, dst, meta,
                              blob, sha)

    def _drain(self) -> None:
        for fr in self.transport.poll(0.0):
            if fr.flow != "activation":
                continue
            h = fr.header
            if hashlib.sha256(fr.blob).hexdigest() != h.get("sha256"):
                continue  # frame-level digest already passed; belt+braces
            key = (h["kind"], int(h["epoch"]), int(h["step"]),
                   int(h["micro"]), int(h["src"]))
            self._pending[key] = (h["meta"], fr.blob)

    def check_epoch(self, epoch: int) -> None:
        if self.epoch_fn is None:
            return
        cur = self.epoch_fn()
        if cur > epoch:
            raise QuiesceSignal(cur)

    def drop_before_epoch(self, epoch: int) -> None:
        """Discard buffered frames from abandoned epochs (quiesce path)."""
        self._pending = {k: v for k, v in self._pending.items()
                         if int(k[1]) >= int(epoch)}

    def recv(self, kind: str, epoch: int, step: int, micro: int,
             src: int, dst: int, template: PyTree) -> PyTree:
        key = (kind, int(epoch), int(step), int(micro), int(src))
        deadline = time.monotonic() + self.deadline_s
        span = self.tracer.span(SpanName.PIPE_EXCHANGE_RECV, kind=kind,
                                micro=micro, from_stage=src) \
            if self.tracer is not None else None
        ctx = span if span is not None else _NullCtx()
        with ctx:
            while True:
                self._drain()
                hit = self._pending.pop(key, None)
                if hit is None:
                    hit = self._spool_read(kind, epoch, step, micro, src)
                if hit is not None:
                    meta, blob = hit
                    return unpack_tree(template, meta, blob)
                self.check_epoch(epoch)
                if time.monotonic() > deadline:
                    raise ExchangeTimeout(
                        f"stage {self.stage}: no {kind} frame for "
                        f"(epoch={epoch}, step={step}, micro={micro}, "
                        f"from={src}) within {self.deadline_s:.1f}s")
                self.transport.wait(0.02)


class _NullCtx:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


# --------------------------------------------------------------------------
# per-stage compiled programs


class StagePrograms:
    """The jitted per-stage programs, shape-specialized once per
    (config, micro geometry) and stage-agnostic thereafter (the stage
    index is traced, so one cache entry serves every stage and survives a
    respawn)."""

    def __init__(self, config, micro_template: PyTree,
                 shared_template: PyTree):
        from ...models import gpt_pipeline

        self.config = config
        self.n_stages = int(config.num_stages)
        self.num_micro = int(config.num_micro_batches)
        stage_fn = partial(gpt_pipeline._stage_fn, config=config)
        embed_fn = partial(gpt_pipeline._embed_fn, config=config)
        loss_head_fn = partial(gpt_pipeline._loss_head_fn, config=config)
        n_stages = self.n_stages

        def pick_micro(micro_inputs, m):
            return jax.tree_util.tree_map(
                lambda x: lax.dynamic_index_in_dim(x, m, axis=0,
                                                   keepdims=False),
                micro_inputs)

        sds = lambda t: jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), t)
        x0sh = jax.eval_shape(
            lambda shp, mi: embed_fn(shp, pick_micro(mi, jnp.int32(0))),
            sds(shared_template), sds(micro_template))
        #: boundary activation shape/dtype (the exchange template)
        self.x_struct = jax.ShapeDtypeStruct(x0sh.shape, x0sh.dtype)

        # dslint: disable=jit-in-hot-path — built once per StagePrograms (one per stage process), reused every 1F1B tick
        @jax.jit
        def stage_fwd(stage, sp, shp, micro_inputs, m, recv_act):
            is_first = stage == 0
            is_last = stage == n_stages - 1
            mb = pick_micro(micro_inputs, m)
            zeros_x = jnp.zeros(x0sh.shape, x0sh.dtype)
            x_in = lax.cond(is_first,
                            lambda: embed_fn(shp, mb).astype(x0sh.dtype),
                            lambda: recv_act)
            y = lax.cond(is_last, lambda: zeros_x, lambda: stage_fn(sp, x_in))
            return x_in, y

        # dslint: disable=jit-in-hot-path — built once per StagePrograms (one per stage process), reused every 1F1B tick
        @jax.jit
        def stage_bwd(stage, sp, shp, micro_inputs, m, x_in, recv_grad,
                      d_stage, d_shared, loss_sum, denom_sum, loss_scale):
            is_first = stage == 0
            is_last = stage == n_stages - 1
            mb = pick_micro(micro_inputs, m)
            zero_scalar = jnp.zeros((), f32)

            def local(sp, shp, x):
                h = lax.cond(is_first,
                             lambda: embed_fn(shp, mb).astype(x.dtype),
                             lambda: x)
                y = stage_fn(sp, h)
                l, d = lax.cond(is_last,
                                lambda: loss_head_fn(shp, y, mb),
                                lambda: (zero_scalar, zero_scalar))
                return y, l, d

            (y, l, d), vjp_fn = jax.vjp(local, sp, shp, x_in)
            g_y = jnp.where(is_last, jnp.zeros_like(recv_grad), recv_grad)
            seed = jnp.asarray(loss_scale, f32)
            dsp, dshp, dx = vjp_fn((g_y, seed, zero_scalar))
            acc = lambda a, g: a + g.astype(f32)
            return (dx.astype(x0sh.dtype),
                    jax.tree_util.tree_map(acc, d_stage, dsp),
                    jax.tree_util.tree_map(acc, d_shared, dshp),
                    loss_sum + l, denom_sum + d)

        # dslint: disable=jit-in-hot-path — built once per StagePrograms (one per stage process), reused every 1F1B tick
        @jax.jit
        def finalize(d_stage, d_shared_summed, loss_sum_total,
                     denom_sum_total):
            denom = jnp.maximum(denom_sum_total, 1.0)
            lossv = loss_sum_total / denom
            inv = 1.0 / denom
            d_stage = jax.tree_util.tree_map(lambda g: g * inv, d_stage)
            d_shared = jax.tree_util.tree_map(lambda g: g * inv,
                                              d_shared_summed)
            return lossv, d_stage, d_shared

        # dslint: disable=jit-in-hot-path,missing-donation — built once per StagePrograms like stage_fwd above; the host keeps the old (params, m, v) until the shard save fences, so donating would alias live buffers
        @jax.jit
        def adam(params, m, v, grads, t, lr, b1, b2, eps):
            # elementwise in fp32: an Adam step on a layer *slice* is
            # bitwise-identical to the same rows of an Adam step on the
            # full stack — what makes per-stage optimizers parity-safe
            t = t.astype(f32)
            b1 = jnp.asarray(b1, f32)
            b2 = jnp.asarray(b2, f32)
            up = lambda p, mm, vv, g: (
                b1 * mm + (1.0 - b1) * g,
                b2 * vv + (1.0 - b2) * g * g)
            new = jax.tree_util.tree_map(
                lambda p, mm, vv, g: _adam_leaf(p, mm, vv, g, t, lr, b1,
                                                b2, eps),
                params, m, v, grads)
            del up
            ps = jax.tree_util.tree_map(lambda x: x[0], new,
                                        is_leaf=lambda x: isinstance(
                                            x, tuple))
            ms = jax.tree_util.tree_map(lambda x: x[1], new,
                                        is_leaf=lambda x: isinstance(
                                            x, tuple))
            vs = jax.tree_util.tree_map(lambda x: x[2], new,
                                        is_leaf=lambda x: isinstance(
                                            x, tuple))
            return ps, ms, vs

        self.stage_fwd = stage_fwd
        self.stage_bwd = stage_bwd
        self.finalize = finalize
        self.adam = adam

    def compile_counts(self) -> Dict[str, int]:
        """Jit-cache entry counts per program — the zero-steady-state-
        recompile gate asserts these stop growing after warmup."""
        return {name: int(getattr(self, name)._cache_size())
                for name in ("stage_fwd", "stage_bwd", "finalize", "adam")}


def _adam_leaf(p, m, v, g, t, lr, b1, b2, eps):
    g = g.astype(f32)
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    mhat = m / (1.0 - b1 ** t)
    vhat = v / (1.0 - b2 ** t)
    return (p - lr * mhat / (jnp.sqrt(vhat) + eps)).astype(p.dtype), m, v


def adam_init(params: PyTree) -> Tuple[PyTree, PyTree]:
    z = lambda p: jnp.zeros(p.shape, f32)
    return (jax.tree_util.tree_map(z, params),
            jax.tree_util.tree_map(z, params))


def slice_stage_params(config, stage: int, stage_params_full: PyTree
                       ) -> PyTree:
    """This stage's contiguous layer slice of the stacked block tree."""
    lper = config.n_layer // config.num_stages
    lo, hi = stage * lper, (stage + 1) * lper
    return jax.tree_util.tree_map(lambda x: x[lo:hi], stage_params_full)


def stack_stage_params(slices: List[PyTree]) -> PyTree:
    """Inverse of :func:`slice_stage_params` over all stages."""
    return jax.tree_util.tree_map(
        lambda *xs: jnp.concatenate([jnp.asarray(x) for x in xs], axis=0),
        *slices)


# --------------------------------------------------------------------------
# the stage worker: one stage's half-step state machine


class StageWorker:
    """One pipeline stage's runtime state + the tick-level 1F1B driver.

    The step is split into ``begin_step`` / ``run_tick`` / ``reduce_send``
    / ``reduce_finish`` so the same state machine serves both executions:
    the local runner interleaves all stages tick by tick in one process;
    a stage process runs its own column start to finish with blocking
    exchange receives.
    """

    def __init__(self, stage: int, config, programs: StagePrograms,
                 stage_params: PyTree, shared_params: PyTree,
                 exchange, journal=None, tracer=None,
                 lr: float = 1e-3, betas: Tuple[float, float] = (0.9, 0.999),
                 eps: float = 1e-8):
        self.stage = int(stage)
        self.config = config
        self.programs = programs
        self.n_stages = programs.n_stages
        self.num_micro = programs.num_micro
        self.exchange = exchange
        self.journal = journal
        self.tracer = tracer
        self.lr = float(lr)
        self.betas = (float(betas[0]), float(betas[1]))
        self.eps = float(eps)
        self.stage_params = stage_params
        self.shared_params = shared_params
        self.stage_m, self.stage_v = adam_init(stage_params)
        self.shared_m, self.shared_v = adam_init(shared_params)
        self.adam_t = 0
        self.epoch = 0
        self.requiesces = 0
        self.fwd_tbl, self.bwd_tbl = schedule_tables(self.num_micro,
                                                     self.n_stages)
        self.ticks = int(self.fwd_tbl.shape[0])
        self._zero_scalar = jnp.zeros((), f32)
        # per-step scratch
        self._micro: Optional[PyTree] = None
        self._step = -1
        self._acts: Dict[int, jnp.ndarray] = {}
        self._d_stage: Optional[PyTree] = None
        self._d_shared: Optional[PyTree] = None
        self._loss_sum = self._zero_scalar
        self._denom_sum = self._zero_scalar

    # -- step protocol ----------------------------------------------------
    def _zeros_x(self) -> jnp.ndarray:
        st = self.programs.x_struct
        return jnp.zeros(st.shape, st.dtype)

    def begin_step(self, step: int, micro_inputs: PyTree) -> None:
        self._step = int(step)
        self._micro = micro_inputs
        self._acts = {}
        zf = lambda t: jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, f32), t)
        self._d_stage = zf(self.stage_params)
        self._d_shared = zf(self.shared_params)
        self._loss_sum = self._zero_scalar
        self._denom_sum = self._zero_scalar

    def run_tick(self, t: int) -> None:
        s = self.stage
        mf = int(self.fwd_tbl[t, s])
        mb = int(self.bwd_tbl[t, s])
        if mf < 0 and mb < 0:
            return
        op = "fwd" if mf >= 0 else "bwd"
        span = self.tracer.span(SpanName.PIPE_TICK, tick=t, op=op) \
            if self.tracer is not None else _NullCtx()
        with span:
            if mf >= 0:
                recv = self._zeros_x() if s == 0 else self.exchange.recv(
                    "act", self.epoch, self._step, mf, s - 1, s,
                    self.programs.x_struct)
                x_in, y = self.programs.stage_fwd(
                    jnp.int32(s), self.stage_params, self.shared_params,
                    self._micro, jnp.int32(mf), recv)
                self._acts[mf] = x_in
                if s < self.n_stages - 1:
                    self.exchange.send("act", self.epoch, self._step, mf,
                                       s, s + 1, y)
            else:
                recvg = self._zeros_x() if s == self.n_stages - 1 else \
                    self.exchange.recv("grad", self.epoch, self._step, mb,
                                       s + 1, s, self.programs.x_struct)
                dx, d, dsh, ls, ds = self.programs.stage_bwd(
                    jnp.int32(s), self.stage_params, self.shared_params,
                    self._micro, jnp.int32(mb), self._acts.pop(mb), recvg,
                    self._d_stage, self._d_shared, self._loss_sum,
                    self._denom_sum, 1.0)
                self._d_stage, self._d_shared = d, dsh
                self._loss_sum, self._denom_sum = ls, ds
                if s > 0:
                    self.exchange.send("grad", self.epoch, self._step, mb,
                                       s, s - 1, dx)

    def _reduce_template(self) -> Tuple[PyTree, Any, Any]:
        sds = lambda t: jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, f32), t)
        sc = jax.ShapeDtypeStruct((), f32)
        return sds(self.shared_params), sc, sc

    def reduce_send(self) -> None:
        if self.stage == 0:
            return
        self.exchange.send("part", self.epoch, self._step, -1, self.stage,
                           0, (self._d_shared, self._loss_sum,
                               self._denom_sum))

    def reduce_finish(self) -> float:
        span = self.tracer.span(SpanName.PIPE_GRAD_REDUCE,
                                step=self._step) \
            if self.tracer is not None else _NullCtx()
        with span:
            if self.stage == 0:
                dsh_total = self._d_shared
                ls_total, ds_total = self._loss_sum, self._denom_sum
                add = lambda a, b: a + b
                # stage-ordered fold — the linear reduction the SPMD psum
                # lowers to, and bitwise-equal to it for two stages
                for src in range(1, self.n_stages):
                    part, ls, ds = self.exchange.recv(
                        "part", self.epoch, self._step, -1, src, 0,
                        self._reduce_template())
                    dsh_total = jax.tree_util.tree_map(add, dsh_total,
                                                       part)
                    ls_total = ls_total + ls
                    ds_total = ds_total + ds
                for dst in range(1, self.n_stages):
                    self.exchange.send("total", self.epoch, self._step,
                                       -1, 0, dst,
                                       (dsh_total, ls_total, ds_total))
            else:
                dsh_total, ls_total, ds_total = self.exchange.recv(
                    "total", self.epoch, self._step, -1, 0, self.stage,
                    self._reduce_template())
        loss, d_stage_f, d_shared_f = self.programs.finalize(
            self._d_stage, dsh_total, ls_total, ds_total)
        t = jnp.int32(self.adam_t + 1)
        self.stage_params, self.stage_m, self.stage_v = self.programs.adam(
            self.stage_params, self.stage_m, self.stage_v, d_stage_f, t,
            self.lr, self.betas[0], self.betas[1], self.eps)
        (self.shared_params, self.shared_m,
         self.shared_v) = self.programs.adam(
            self.shared_params, self.shared_m, self.shared_v, d_shared_f,
            t, self.lr, self.betas[0], self.betas[1], self.eps)
        self.adam_t += 1
        return float(loss)

    def train_step(self, step: int, micro_inputs: PyTree) -> float:
        """Full step for the subprocess runner (blocking exchanges)."""
        span = self.tracer.span(SpanName.PIPE_STEP, step=step,
                                stage=self.stage) \
            if self.tracer is not None else _NullCtx()
        with span:
            self.begin_step(step, micro_inputs)
            for t in range(self.ticks):
                self.run_tick(t)
            self.reduce_send()
            return self.reduce_finish()

    def abandon_step(self) -> None:
        """Drop the in-flight step's scratch (quiesce path): partial
        accumulators and stashed activations must not survive into the
        replayed step."""
        self._micro = None
        self._step = -1
        self._acts = {}
        self._d_stage = None
        self._d_shared = None
        self._loss_sum = self._zero_scalar
        self._denom_sum = self._zero_scalar

    # -- state (for checkpoints) ------------------------------------------
    def state_trees(self) -> Dict[str, PyTree]:
        return {"stage": self.stage_params, "stage_m": self.stage_m,
                "stage_v": self.stage_v, "shared": self.shared_params,
                "shared_m": self.shared_m, "shared_v": self.shared_v}

    def load_state_trees(self, trees: Dict[str, PyTree],
                         adam_t: int) -> None:
        self.stage_params = trees["stage"]
        self.stage_m = trees["stage_m"]
        self.stage_v = trees["stage_v"]
        self.shared_params = trees["shared"]
        self.shared_m = trees["shared_m"]
        self.shared_v = trees["shared_v"]
        self.adam_t = int(adam_t)


# --------------------------------------------------------------------------
# per-stage checkpoint shards (two-phase committed by commit.py)


def save_stage_shard(save_dir: str, tag: str, stage: int,
                     worker: StageWorker, step: int,
                     loader_state: Optional[Dict[str, Any]] = None) -> str:
    """Atomically write this stage's shard under ``save_dir/tag/`` —
    the rank-manifest vote and marker publish are the caller's job
    (``checkpoint_engine/commit.py``)."""
    d = os.path.join(save_dir, tag)
    os.makedirs(d, exist_ok=True)
    arrays: Dict[str, np.ndarray] = {}
    for name, tree in worker.state_trees().items():
        for i, leaf in enumerate(jax.tree_util.tree_leaves(tree)):
            arrays[f"{name}.{i}"] = np.asarray(jax.device_get(leaf))
    arrays["step"] = np.asarray(int(step), np.int64)
    arrays["adam_t"] = np.asarray(int(worker.adam_t), np.int64)
    path = os.path.join(d, f"stage{stage}.npz")
    tmp = path + ".tmp.npz"
    np.savez(tmp, **arrays)
    os.replace(tmp, path)
    if loader_state is not None:
        lpath = os.path.join(d, f"stage{stage}.loader.json")
        tmp = lpath + ".tmp"
        with open(tmp, "w") as f:
            json.dump(loader_state, f)
        os.replace(tmp, lpath)
    return path


def load_stage_shard(save_dir: str, tag: str, stage: int,
                     worker: StageWorker) -> Tuple[int,
                                                   Optional[Dict[str, Any]]]:
    """Restore this stage's state from a committed tag; returns
    ``(step, loader_state)``."""
    d = os.path.join(save_dir, tag)
    with np.load(os.path.join(d, f"stage{stage}.npz")) as z:
        trees: Dict[str, PyTree] = {}
        for name, tmpl in worker.state_trees().items():
            flat, treedef = jax.tree_util.tree_flatten(tmpl)
            leaves = [jnp.asarray(z[f"{name}.{i}"])
                      for i in range(len(flat))]
            trees[name] = jax.tree_util.tree_unflatten(treedef, leaves)
        step = int(z["step"])
        adam_t = int(z["adam_t"])
    worker.load_state_trees(trees, adam_t)
    loader_state = None
    lpath = os.path.join(d, f"stage{stage}.loader.json")
    if os.path.exists(lpath):
        with open(lpath) as f:
            loader_state = json.load(f)
    return step, loader_state


# --------------------------------------------------------------------------
# local (single-process) MPMD runner — the parity fixture and mfu probe


class LocalPipeline:
    """All stage workers in one process over a :class:`LoopbackExchange`,
    interleaved tick by tick — the MPMD executor with the sockets swapped
    out, used by the parity tests and the CPU bench fixture."""

    def __init__(self, config, params: PyTree, lr: float = 1e-3,
                 betas: Tuple[float, float] = (0.9, 0.999),
                 eps: float = 1e-8):
        from ...models import gpt_pipeline

        self.config = config
        self._split_micro = partial(gpt_pipeline._split_micro, config)
        stage_full, shared = gpt_pipeline.split_params(config, params)
        micro_tmpl = None  # built lazily from the first batch
        self._micro_tmpl = micro_tmpl
        self._stage_full_struct = stage_full
        self._shared = shared
        self._lr, self._betas, self._eps = lr, betas, eps
        self.exchange = LoopbackExchange()
        self.programs: Optional[StagePrograms] = None
        self.workers: List[StageWorker] = []

    def _build(self, micro: PyTree) -> None:
        self.programs = StagePrograms(self.config, micro, self._shared)
        self.workers = [
            StageWorker(s, self.config, self.programs,
                        slice_stage_params(self.config, s,
                                           self._stage_full_struct),
                        self._shared, self.exchange, lr=self._lr,
                        betas=self._betas, eps=self._eps)
            for s in range(self.config.num_stages)]

    def train_step(self, step: int, batch: Dict[str, jnp.ndarray]) -> float:
        micro = self._split_micro(batch)
        if self.programs is None:
            self._build(micro)
        ws = self.workers
        for w in ws:
            w.begin_step(step, micro)
        for t in range(ws[0].ticks):
            for w in ws:
                w.run_tick(t)
        for w in ws:
            w.reduce_send()
        loss = ws[0].reduce_finish()
        for w in ws[1:]:
            w.reduce_finish()
        return loss

    def params(self) -> PyTree:
        """Reassemble the full parameter tree (stacked blocks + shared)."""
        assert self.workers, "no step has run yet"
        stacked = stack_stage_params([w.stage_params for w in self.workers])
        out = dict(self.workers[0].shared_params)
        out["blocks"] = stacked["blocks"]
        return out

    def compile_counts(self) -> Dict[str, int]:
        assert self.programs is not None
        return self.programs.compile_counts()
