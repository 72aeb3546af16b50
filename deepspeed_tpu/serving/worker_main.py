"""Serving-fleet worker entry: ``python -m deepspeed_tpu.serving.worker_main``.

Spawned by :class:`~deepspeed_tpu.serving.fleet.ServeFleetSupervisor`,
one process per role instance.  Contract via environment:

========================  ====================================================
``DS_SERVE_CONFIG``       path to the run's ``serve_fleet.json``
``DS_SERVE_ROLE``         ``"prefill"`` or ``"decode"``
``DS_SERVE_RANK``         fleet rank (decode engines = ``0..n_decode-1``,
                          prefill = ``n_decode..n_decode+n_prefill-1``)
``DS_SERVE_INC``          incarnation number (bumped by each respawn)
``DS_FAULT_PLAN``         scenario faults, armed at import by
                          ``fault_injection.install_env_plan``
========================  ====================================================

Every role builds the *identical* tiny-GPT fixture from the shared seed —
that determinism is what makes a prefill worker's KV page bundle bitwise
equivalent to a local prefill on the decode engine.

A **prefill** worker drains its spool inbox: chunked-prefill the prompt's
first ``S-1`` tokens (firing ``serve.prefill_chunk`` before each chunk —
the kill/straggler fault point), publish the KV as a digest-manifested
page bundle, journal ``serve.fleet.bundle``.

A **decode** engine runs the ``SlotBatcher`` tick loop (firing
``serve.decode_tick`` each round) over its private inbox
(``spool/decode/d<rank>``): admit orders — bundle orders rebuild the
pages into a batch-1 cache and ride the prefix-resume path; corrupt
bundles are nacked back to the supervisor for re-prefill
(``serve.fleet.bundle_reject``), never decoded; ``local`` orders prefill
in place (the degraded path); **migration** orders (``mig`` set) verify
and readmit a session another engine parked, seeding its already-emitted
tokens so the conversation resumes bitwise mid-decode.  ``park``
commands export a held session's KV as a digest-manifested migration
bundle (``serve.fleet.migrate``) and release the slot; a corrupt
migration bundle nacks as ``serve.fleet.migrate_reject``.  Results land
as spool files; order files are never deleted, so a respawned
incarnation rescans, skips requests whose results already landed *and*
any order superseded by a newer route marker
(``spool/decode/routes/``), and re-admits the rest — that is the whole
decode-bounce requeue story.  ``decode.stats.r<rank>.json`` snapshots
compile counts after warmup and after every completion, so tests can
assert zero steady-state recompiles per engine; a
``metrics.rank<rank>.jsonl`` stream publishes slot occupancy /
queue depth — the router's load signal for placing new sessions.
"""

from __future__ import annotations

import json
import os
import sys
import time


def _env() -> dict:
    with open(os.environ["DS_SERVE_CONFIG"]) as f:
        cfg = json.load(f)
    cfg["role"] = os.environ["DS_SERVE_ROLE"]
    cfg["rank"] = int(os.environ["DS_SERVE_RANK"])
    cfg["incarnation"] = int(os.environ.get("DS_SERVE_INC", "0"))
    return cfg


def _build_batcher(cfg: dict, slots: int):
    """The shared tiny-GPT fixture + a SlotBatcher over it — identical
    across processes given the identical config payload."""
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.models import gpt
    from deepspeed_tpu.serving.batcher import SlotBatcher
    from deepspeed_tpu.serving.config import ServingConfig
    model_cfg = gpt.GPTConfig(
        vocab_size=256, max_seq_len=int(cfg["max_len"]),
        n_layer=int(cfg["n_layer"]), n_head=int(cfg["n_head"]),
        d_model=int(cfg["d_model"]), dtype=jnp.float32, vocab_round_to=128)
    params = gpt.init(model_cfg, jax.random.PRNGKey(int(cfg["seed"])))
    engine = deepspeed_tpu.init_inference(model=(model_cfg, params),
                                          config={"dtype": "float32"})
    scfg = ServingConfig(slots=slots, max_len=int(cfg["max_len"]),
                         prefill_chunk=int(cfg["prefill_chunk"]))
    return SlotBatcher(engine, scfg)


def _mark_ready(ready_dir: str, role: str, rank: int, inc: int) -> None:
    from deepspeed_tpu.runtime.checkpoint_engine.storage import \
        atomic_write_text
    from deepspeed_tpu.telemetry.propagate import clock_sync
    doc = {"role": role, "rank": rank, "incarnation": inc,
           "ts": time.time()}
    # wall/monotonic handshake: lets the merge step rebase this process's
    # monotonic span timestamps onto the shared wall clock
    doc["clock_sync"] = clock_sync()
    atomic_write_text(os.path.join(ready_dir, f"{role}{rank}.json"),
                      json.dumps(doc))


def _stop_requested(spool: str, role: str = "", rank: int = -1) -> bool:
    """Global fleet stop — or the worker's per-instance stop file: the
    rolling-restart drain signal for a decode engine, the autoscale
    retirement signal for a prefill worker."""
    from deepspeed_tpu.serving.fleet import STOP_NAME
    if os.path.exists(os.path.join(spool, STOP_NAME)):
        return True
    return role in ("decode", "prefill") and os.path.exists(
        os.path.join(spool, f"{STOP_NAME}.{role}{rank}"))


def _scan_orders(inbox: str):
    try:
        names = sorted(os.listdir(inbox))
    except OSError:
        return []
    return [n for n in names if n.endswith(".json")]


# ------------------------------------------------------------------ prefill


def _drain_order_frames(transport, net_orders: dict, journal=None,
                        bundles_dir: str = "") -> None:
    """Pull streamed order frames into ``net_orders`` (name → doc) so the
    scan loop processes them exactly like spool files.  Bundle frames
    materialize their npz blob (digest-verified against the manifest
    ``sha256``) before the order becomes visible; a blob failing that
    check journals a frame-level ``serve.fleet.bundle_reject`` and the
    order rides the publisher's spool copy instead."""
    if transport is None:
        return
    from deepspeed_tpu.runtime.supervision.events import EventKind
    for fr in transport.poll():
        h = fr.header
        doc = h.get("doc")
        name = h.get("name")
        if h.get("what") != "order" or not isinstance(doc, dict) \
                or not isinstance(name, str) or not name:
            continue
        if fr.flow == "bundle" and fr.blob and doc.get("bundle") \
                and bundles_dir:
            ok = transport.store_bundle_blob(
                os.path.join(bundles_dir, str(doc["bundle"])), fr.blob,
                str(doc.get("sha256")))
            if not ok and journal is not None:
                journal.emit(EventKind.SERVE_FLEET_BUNDLE_REJECT,
                             request_id=doc.get("rid"),
                             worker=doc.get("prefill_worker"),
                             attempt=doc.get("attempt"),
                             reason="frame_digest_mismatch", frame=True,
                             trace=None)
        net_orders[name] = doc


def _idle_wait(transport, seconds: float) -> None:
    """Idle like ``time.sleep`` but wake immediately on inbound frames."""
    if transport is None:
        time.sleep(seconds)
    else:
        transport.wait(seconds)


def _prefill_loop(cfg: dict, batcher, journal, spool: str,
                  tracer=None, transport=None) -> None:
    import numpy as np
    from deepspeed_tpu.runtime.supervision.events import EventKind
    from deepspeed_tpu.serving.fleet import SUPERVISOR_RANK, publish_bundle
    from deepspeed_tpu.serving.paging import _host_banks
    from deepspeed_tpu.telemetry.propagate import extract
    from deepspeed_tpu.telemetry.spans import SpanName, Tracer
    from deepspeed_tpu.utils import fault_injection
    tracer = tracer or Tracer(enabled=False)
    rank = cfg["rank"]
    inbox = os.path.join(spool, "prefill", f"w{rank}")
    bundles_dir = os.path.join(spool, "bundles")
    C = batcher.chunk
    # warm every program this role uses (prefill, extend)
    # BEFORE publishing readiness — the supervisor's prefill timeout must
    # clock prefill work, not first-order compilation
    batcher.build_prefix(np.arange(2 * C, dtype=np.int32) % 256)
    _mark_ready(os.path.join(spool, "ready"), "prefill", rank,
                cfg["incarnation"])
    seen = set()
    net_orders: dict = {}     # streamed copies of spool orders, by name
    chunks_done = 0           # worker-global: KillAtStep lands mid-prefill
    while not _stop_requested(spool, "prefill", rank):
        worked = False
        _drain_order_frames(transport, net_orders, journal=journal)
        for name in sorted(set(_scan_orders(inbox)) | set(net_orders)):
            if name in seen:
                net_orders.pop(name, None)
                continue
            order = net_orders.pop(name, None)
            if order is None:
                try:
                    with open(os.path.join(inbox, name)) as f:
                        order = json.load(f)
                except (OSError, ValueError):
                    continue  # torn/being-replaced — next scan gets it
            seen.add(name)
            worked = True
            rid, attempt = order["rid"], int(order["attempt"])
            # absent/malformed context (old spools) → fresh root span
            ctx = extract(order)
            tfields = ctx.fields() if ctx is not None else {}
            tokens = np.asarray(order["tokens"], np.int32)
            prefix = tokens[:-1]          # last token stays with decode
            cache, frontier = None, 0
            t_start = time.time()
            with tracer.span(SpanName.SERVE_FLEET_PREFILL, request_id=rid,
                             attempt=attempt, **tfields):
                for pos in range(0, int(prefix.shape[0]), C):
                    fault_injection.fire("serve.prefill_chunk",
                                         step=chunks_done, path=rid)
                    cache, frontier = batcher._chunked_prefill(
                        prefix[pos:pos + C], start_cache=cache,
                        start_len=pos)
                    chunks_done += 1
            t_prefilled = time.time()
            with tracer.span(SpanName.SERVE_FLEET_PUBLISH, request_id=rid,
                             attempt=attempt, **tfields):
                banks = _host_banks(cache, frontier)
                # t_start/prefill_s ride the manifest so the supervisor's
                # autoscaler can decompose TTFT into queue_wait vs prefill
                # without waiting for the journal to flush
                manifest = publish_bundle(
                    bundles_dir, rid, attempt, banks, prefix, frontier,
                    worker=rank, trace=ctx,
                    extra={"t_start": t_start,
                           "prefill_s": round(t_prefilled - t_start, 6)})
            t_published = time.time()
            journal.emit(EventKind.SERVE_FLEET_BUNDLE, request_id=rid,
                         worker=rank, attempt=attempt,
                         prefix_len=manifest["prefix_len"],
                         nbytes=manifest["nbytes"],
                         t_start=t_start,
                         prefill_s=round(t_prefilled - t_start, 6),
                         publish_s=round(t_published - t_prefilled, 6),
                         trace=tfields or None)
            if transport is not None:
                # stream the manifest so the supervisor routes without
                # waiting out a spool-poll interval; the spool copy
                # written above stays authoritative on any drop
                with tracer.span(SpanName.SERVE_TRANSPORT_SEND,
                                 request_id=rid, flow="result",
                                 **tfields):
                    transport.send("result", "sup", SUPERVISOR_RANK,
                                   {"what": "manifest", "doc": manifest})
        if not worked:
            _idle_wait(transport, 0.02)


# ------------------------------------------------------------------- decode


def _write_stats(run_dir: str, rank: int, inc: int, warm: dict, batcher,
                 ticks: int) -> None:
    from deepspeed_tpu.runtime.checkpoint_engine.storage import \
        atomic_write_text
    atomic_write_text(os.path.join(run_dir, f"decode.stats.r{rank}.json"),
                      json.dumps({"rank": rank, "incarnation": inc,
                                  "warm": warm,
                                  "now": batcher.compile_counts(),
                                  "ticks": ticks}, sort_keys=True))


def _append_metrics(run_dir: str, rank: int, inc: int, active: int,
                    free_slots: int, queue_depth: int, ticks: int) -> None:
    """One load sample on the engine's ``metrics.rank<N>.jsonl`` stream —
    what the supervisor's router tails to place new sessions (and what
    ``fleet_report`` renders as a metrics track)."""
    row = {"ts": time.time(), "rank": rank, "role": "decode",
           "incarnation": inc, "active": active, "free_slots": free_slots,
           "queue_depth": queue_depth, "ticks": ticks}
    with open(os.path.join(run_dir, f"metrics.rank{rank}.jsonl"),
              "a", encoding="utf-8") as f:
        f.write(json.dumps(row, sort_keys=True) + "\n")


def _decode_loop(cfg: dict, batcher, journal, spool: str,
                 tracer=None, transport=None) -> None:
    import jax
    import numpy as np
    from deepspeed_tpu.runtime.checkpoint_engine.storage import \
        atomic_write_text
    from deepspeed_tpu.runtime.supervision.events import EventKind
    from deepspeed_tpu.serving.batcher import PrefixEntry
    from deepspeed_tpu.serving.fleet import (SUPERVISOR_RANK,
                                             BundleCorruptError,
                                             bundle_paths, load_bundle,
                                             publish_bundle,
                                             rebuild_prefix_cache)
    from deepspeed_tpu.serving.paging import _slot_banks
    from deepspeed_tpu.serving.routing import order_is_current
    from deepspeed_tpu.telemetry.propagate import extract
    from deepspeed_tpu.telemetry.spans import SpanName, Tracer
    from deepspeed_tpu.utils import fault_injection
    tracer = tracer or Tracer(enabled=False)
    rank, inc = cfg["rank"], cfg["incarnation"]
    run_dir = cfg["run_dir"]
    decode_root = os.path.join(spool, "decode")
    inbox = os.path.join(decode_root, f"d{rank}")
    bundles_dir = os.path.join(spool, "bundles")
    results_dir = os.path.join(spool, "results")
    C, slots = batcher.chunk, int(cfg["slots"])
    metrics_interval = float(cfg.get("metrics_interval_s", 0.2))

    # warm EVERY decode-path program (the admission over a 2-chunk prompt,
    # tick, release, and the admission that continues a prefix, as every
    # bundle order's does: here the row just admitted, read back) before
    # declaring ready — steady state must be compile-free, and the stats
    # snapshot below is what the recompile test pins against
    warm_tokens = np.arange(C + 2, dtype=np.int32) % 256
    warm_key = jax.random.PRNGKey(0)
    batcher.admit(0, warm_tokens, warm_key, greedy=True, temperature=1.0)
    batcher.tick()
    batcher.release(0)
    batcher.admit(0, warm_tokens, warm_key, greedy=True, temperature=1.0,
                  prefix=PrefixEntry(cache=batcher._fam.read_slot(
                      batcher.cache, 0, C), length=C))
    batcher.release(0)
    warm = batcher.compile_counts()
    _write_stats(run_dir, rank, inc, warm, batcher, 0)
    _mark_ready(os.path.join(spool, "ready"), "decode", rank, inc)

    free = list(range(slots))
    active: dict = {}         # row -> request state
    seen = set()              # (rid, d) admitted/nacked, parks this life
    net_orders: dict = {}     # streamed copies of spool orders, by name
    ticks = 0
    admits = 0                # serve.admit fault-step counter
    next_metrics = 0.0

    def _nack(path: str, doc: dict, what: str = "") -> None:
        atomic_write_text(path, json.dumps(doc, sort_keys=True))
        if transport is not None and what:
            # stream the spool-durable ack/nack so the supervisor reacts
            # this poll instead of next scan
            transport.send("result", "sup", SUPERVISOR_RANK,
                           {"what": what, "doc": doc})

    def _park(order: dict) -> None:
        """Handle one park command: export the held session's KV as a
        migration bundle (+ resume state) and release the slot — or ack
        ``done``/``unheld`` so the supervisor can finish or re-route."""
        rid, mig = order["rid"], int(order["mig"])
        key = (rid, "park", mig)
        if key in seen:
            return
        # a stale park (the supervisor re-routed past this engine) is
        # ignored without an ack — its mig ack path was abandoned too
        if not order_is_current(decode_root, rid, int(order.get("d", 0)),
                                rank):
            seen.add(key)
            return
        ctx = extract(order)
        tfields = ctx.fields() if ctx is not None else {}
        ack_path = bundle_paths(bundles_dir, rid, mig, tag="m")[1]
        if os.path.exists(os.path.join(results_dir, f"{rid}.json")):
            seen.add(key)
            _nack(ack_path, {"rid": rid, "mig": mig, "state": "done"},
                  what="mig_ack")
            return
        row = next((r for r, st in active.items() if st["rid"] == rid),
                   None)
        if row is None:
            seen.add(key)
            _nack(ack_path, {"rid": rid, "mig": mig, "state": "unheld"},
                  what="mig_ack")
            return
        seen.add(key)
        st = active[row]
        fault_injection.fire("serve.migrate_export", request_id=rid,
                             mig=mig)
        t_park = time.time()
        with tracer.span(SpanName.SERVE_PARK, request_id=rid, mig=mig,
                         **tfields):
            # frontier F = prompt + tokens emitted so far; export the
            # first F-1 KV rows — the target re-prefills the final token,
            # regenerating the sampling logits bitwise
            full = np.concatenate(
                [st["tokens"], np.asarray(st["out"], np.int32)])
            F = int(full.shape[0])
            banks = _slot_banks(batcher.cache, row, F - 1)
            manifest = publish_bundle(
                bundles_dir, rid, mig, banks, full[:F - 1], F - 1,
                worker=rank, trace=ctx, tag="m",
                extra={"state": "exported", "mig": mig, "t_park": t_park,
                       "resume": {"out": list(st["out"]),
                                  "t_first": st["first_ts"]}})
        journal.emit(EventKind.SERVE_FLEET_MIGRATE, request_id=rid,
                     from_worker=rank,
                     to_worker=order.get("to_worker"), mig=mig,
                     state="exported", nbytes=manifest["nbytes"],
                     reason=order.get("reason"), t_park=t_park,
                     export_s=round(time.time() - t_park, 6),
                     trace=tfields or None)
        if transport is not None:
            # the exported manifest IS the park ack — stream it so the
            # supervisor re-routes without a spool-poll round trip
            with tracer.span(SpanName.SERVE_TRANSPORT_SEND,
                             request_id=rid, flow="result", **tfields):
                transport.send("result", "sup", SUPERVISOR_RANK,
                               {"what": "mig_ack", "doc": manifest})
        batcher.release(row)
        free.append(row)
        del active[row]

    while True:
        if _stop_requested(spool, "decode", rank) and not active:
            break
        now_wall = time.time()
        if now_wall >= next_metrics:
            _append_metrics(run_dir, rank, inc, len(active), len(free),
                            0, ticks)
            next_metrics = now_wall + metrics_interval
        # ---- admissions (skip anything already resulted or superseded
        # by a newer route marker: the respawn-rescan path — orders
        # persist, completions and re-routed stragglers don't repeat)
        waiting = 0
        _drain_order_frames(transport, net_orders, journal=journal,
                            bundles_dir=bundles_dir)
        for name in sorted(set(_scan_orders(inbox)) | set(net_orders)):
            order = net_orders.get(name)
            via = "stream" if order is not None else "spool"
            if order is None:
                try:
                    with open(os.path.join(inbox, name)) as f:
                        order = json.load(f)
                except (OSError, ValueError):
                    continue  # torn/being-replaced — next scan gets it
            if order.get("cmd") == "park":
                _park(order)
                net_orders.pop(name, None)
                continue
            rid, d = order["rid"], int(order.get("d", 0))
            if (rid, d) in seen:
                net_orders.pop(name, None)
                continue
            if os.path.exists(os.path.join(results_dir, f"{rid}.json")):
                seen.add((rid, d))
                net_orders.pop(name, None)
                continue
            if not order_is_current(decode_root, rid, d, rank):
                # superseded straggler (re-routed or migrated away while
                # this engine was down, or a stale frame outrun by a newer
                # route marker) — never double-decode it
                seen.add((rid, d))
                net_orders.pop(name, None)
                continue
            if not free:
                waiting += 1
                continue      # revisit once a slot frees up
            seen.add((rid, d))
            net_orders.pop(name, None)
            attempt = int(order["attempt"])
            mig = order.get("mig")
            t_order = time.time()
            fault_injection.fire("serve.admit", step=admits,
                                 request_id=rid, slot=None)
            admits += 1
            # absent/malformed context (old spools) → fresh root span
            ctx = extract(order)
            tfields = ctx.fields() if ctx is not None else {}
            tokens = np.asarray(order["tokens"], np.int32)
            prefix = None
            verify_ms = 0.0
            if order.get("bundle"):
                npz_path = os.path.join(bundles_dir, order["bundle"])
                if mig is not None:
                    fault_injection.fire("serve.migrate_admit",
                                         path=npz_path, request_id=rid,
                                         mig=int(mig))
                try:
                    t_verify = time.time()
                    with tracer.span(SpanName.SERVE_FLEET_VERIFY,
                                     request_id=rid, attempt=attempt,
                                     **tfields):
                        banks, btoks, blen = load_bundle(
                            npz_path, expect_digest=order.get("sha256"))
                        if blen != int(tokens.shape[0]) - 1 or \
                                not np.array_equal(btoks[:blen],
                                                   tokens[:blen]):
                            raise BundleCorruptError(
                                f"bundle prefix mismatch for {rid}")
                        prefix = PrefixEntry(
                            cache=rebuild_prefix_cache(batcher, banks, blen),
                            length=blen)
                    verify_ms = round((time.time() - t_verify) * 1000.0, 3)
                except BundleCorruptError as e:
                    if mig is not None:
                        # migration bitrot → nack into a re-prefill: a
                        # retry, never a wrong answer
                        journal.emit(EventKind.SERVE_FLEET_MIGRATE_REJECT,
                                     request_id=rid, worker=rank,
                                     mig=int(mig), reason=str(e)[:200],
                                     trace=tfields or None)
                        _nack(os.path.join(
                            results_dir, f"{rid}.m{int(mig)}.nack.json"),
                            {"rid": rid, "mig": int(mig),
                             "reason": str(e)[:200]}, what="mig_nack")
                    else:
                        journal.emit(EventKind.SERVE_FLEET_BUNDLE_REJECT,
                                     request_id=rid,
                                     worker=order.get("prefill_worker"),
                                     attempt=attempt, reason=str(e)[:200],
                                     trace=tfields or None)
                        _nack(os.path.join(
                            results_dir, f"{rid}.a{attempt}.nack.json"),
                            {"rid": rid, "attempt": attempt,
                             "reason": str(e)[:200]}, what="nack")
                    continue
            row = free.pop()
            t_admit = time.time()
            key = jax.random.PRNGKey(int(order.get("seed", 0)))
            with tracer.span(SpanName.SERVE_ADMIT, request_id=rid,
                             slot=row, **tfields):
                batcher.admit(row, tokens, key,
                              greedy=bool(order.get("greedy", True)),
                              temperature=float(
                                  order.get("temperature", 1.0)),
                              prefix=prefix)
            journal.emit(EventKind.SERVE_ADMIT, request_id=rid, slot=row,
                         queued_ms=round(
                             (t_admit - order["t_submit"]) * 1000.0, 1),
                         prefix_hit=prefix is not None,
                         attempt=attempt, t_order=t_order,
                         verify_ms=verify_ms, mig=mig, via=via,
                         trace=tfields or None)
            resume = order.get("resume") or {}
            r_out = [int(t) for t in resume.get("out", [])]
            # a migration order's tokens = prompt + tokens already out;
            # keep only the prompt so a re-park recomputes the frontier
            # from prompt + live out without double-counting
            prompt = tokens[:int(tokens.shape[0]) - len(r_out)] \
                if r_out else tokens
            active[row] = {"rid": rid, "attempt": attempt,
                           "tokens": prompt, "out": r_out,
                           "budget": int(order.get("max_new_tokens", 8)),
                           "t_submit": float(order["t_submit"]),
                           "t_admit": t_admit,
                           "first_ts": resume.get("t_first"),
                           "trace": tfields or None}
        if waiting:
            _append_metrics(run_dir, rank, inc, len(active), len(free),
                            waiting, ticks)
            next_metrics = time.time() + metrics_interval
        # ---- one decode round
        if not active:
            _idle_wait(transport, 0.01)
            continue
        fault_injection.fire("serve.decode_tick", step=ticks, tick=ticks,
                             active=len(active))
        with tracer.span(SpanName.SERVE_TICK, tick=ticks,
                         active=len(active)):
            toks = batcher.tick()
        ticks += 1
        now = time.time()
        for row in list(active):
            st = active[row]
            st["out"].append(int(toks[row]))
            if st["first_ts"] is None:
                st["first_ts"] = now
            if len(st["out"]) < st["budget"]:
                continue
            ttft_ms = (st["first_ts"] - st["t_submit"]) * 1000.0
            rate = len(st["out"]) / max(now - st["t_admit"], 1e-9)
            result_doc = {"rid": st["rid"], "attempt": st["attempt"],
                          "tokens": st["out"],
                          "ttft_ms": round(ttft_ms, 1),
                          "t_done": now, "incarnation": inc}
            atomic_write_text(
                os.path.join(results_dir, f"{st['rid']}.json"),
                json.dumps(result_doc, sort_keys=True))
            if transport is not None:
                with tracer.span(SpanName.SERVE_TRANSPORT_SEND,
                                 request_id=st["rid"], flow="result",
                                 **(st["trace"] or {})):
                    transport.send("result", "sup", SUPERVISOR_RANK,
                                   {"what": "result", "doc": result_doc})
            journal.emit(EventKind.SERVE_DONE, request_id=st["rid"],
                         slot=row, tokens_out=len(st["out"]),
                         ttft_ms=round(ttft_ms, 1),
                         tok_per_s=round(rate, 1),
                         t_first=st["first_ts"], trace=st["trace"])
            batcher.release(row)
            free.append(row)
            del active[row]
            _write_stats(run_dir, rank, inc, warm, batcher, ticks)


# --------------------------------------------------------------------- main


def main() -> int:
    cfg = _env()
    from deepspeed_tpu.utils.platform import force_cpu_platform
    force_cpu_platform(n_devices=1, persistent_cache=False)
    # importing fault_injection arms DS_FAULT_PLAN for this incarnation
    from deepspeed_tpu.utils import fault_injection  # noqa: F401
    from deepspeed_tpu.runtime.checkpoint_engine.storage import \
        atomic_write_text
    from deepspeed_tpu.runtime.supervision.events import (EventJournal,
                                                          EventKind)
    from deepspeed_tpu.runtime.supervision.heartbeat import HeartbeatWriter
    from deepspeed_tpu.runtime.transport import FleetTransport
    from deepspeed_tpu.serving.config import TransportConfig
    from deepspeed_tpu.telemetry.export import write_trace
    from deepspeed_tpu.telemetry.propagate import clock_sync
    from deepspeed_tpu.telemetry.spans import Tracer

    role, rank, inc = cfg["role"], cfg["rank"], cfg["incarnation"]
    run_dir = cfg["run_dir"]
    spool = os.path.join(run_dir, "spool")
    journal = EventJournal(os.path.join(run_dir, "events.jsonl"), rank=rank)
    writer = HeartbeatWriter(os.path.join(run_dir, "heartbeats"), rank,
                             interval_s=float(cfg["heartbeat_interval_s"]),
                             journal=journal).start()
    tracer = Tracer(name=f"{role}{rank}")
    tcfg = TransportConfig.from_dict(cfg.get("transport") or {}).to_dict()
    transport = None
    if tcfg.get("enabled"):
        # announce this incarnation's endpoint before warmup so the
        # supervisor's next (re)connect resolves the fresh port
        transport = FleetTransport(tcfg, run_dir, role, rank,
                                   journal=journal)
    try:
        batcher = _build_batcher(
            cfg, slots=int(cfg["slots"]) if role == "decode" else 1)
        if role == "decode":
            _decode_loop(cfg, batcher, journal, spool, tracer=tracer,
                         transport=transport)
        else:
            _prefill_loop(cfg, batcher, journal, spool, tracer=tracer,
                          transport=transport)
    finally:
        writer.stop()
        if transport is not None:
            try:
                journal.emit(EventKind.METRICS_SAMPLE,
                             m=transport.metrics_sample())
            except (OSError, ValueError):  # dslint: disable=swallowed-exception — telemetry never masks the exit path
                pass
            transport.close()
        # per-incarnation span export with the wall/monotonic handshake
        # fleet_report needs to rebase this process onto the shared clock
        try:
            write_trace(
                os.path.join(run_dir, f"trace.{role}{rank}.inc{inc}.json"),
                tracer,
                extra={"clockSync": dict(clock_sync(), role=role, rank=rank,
                                         incarnation=inc)})
        except (OSError, ValueError) as e:
            # telemetry must never mask the worker's exit path
            from deepspeed_tpu.utils.logging import logger
            logger.warning(f"[serve-fleet] trace export failed: {e}")
    atomic_write_text(os.path.join(run_dir, f"{role}{rank}.exit.json"),
                      json.dumps({"role": role, "rank": rank,
                                  "incarnation": inc, "status": "done"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
