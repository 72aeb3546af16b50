"""Per-rule fixture tests: every dslint rule fires on its known-bad
snippet, stays quiet on the good variant, and honors inline suppression.
The registries are injected so these tests pin the rules' behavior, not
the current contents of events.py / fault_injection.py (the real-tree
interaction is ``test_dslint_tree.py``)."""

import textwrap

from tools.dslint import Project, lint_source

PROJECT = Project(
    event_kind_map={"ROLLBACK": "rollback", "DATA_BATCH": "data.batch"},
    fault_points={"ckpt.write", "data.next"},
    bucketing_helpers={"bucket_max_new_tokens", "bucket_cache_len",
                       "tile_cache_len"},
    lock_name_map={"SERVE_GATEWAY": "serve.gateway",
                   "SERVE_METRICS": "serve.metrics",
                   "TELEMETRY_REGISTRY": "telemetry.registry",
                   "JOURNAL_EMIT": "journal.emit"},
    lock_order=("serve.gateway", "serve.metrics", "telemetry.registry",
                "journal.emit"),
)

CKPT = "deepspeed_tpu/runtime/checkpoint_engine/fixture.py"
SUP = "deepspeed_tpu/runtime/supervision/fixture.py"
DATA = "deepspeed_tpu/runtime/data_pipeline/fixture.py"
COMM = "deepspeed_tpu/comm/comm.py"
OTHER = "deepspeed_tpu/runtime/fixture.py"
INF = "deepspeed_tpu/inference/fixture.py"
SERVE = "deepspeed_tpu/serving/fixture.py"


def lint(src, relpath):
    return lint_source(textwrap.dedent(src), relpath, PROJECT)


def rules_of(findings):
    return [f.rule for f in findings]


# ------------------------------------------------------- swallowed-exception
def test_swallowed_exception_fires_on_bare_pass():
    findings = lint("""
        try:
            risky()
        except OSError:
            pass
    """, CKPT)
    assert rules_of(findings) == ["swallowed-exception"]
    assert findings[0].line == 4  # the `except` line
    assert findings[0].path == CKPT


def test_swallowed_exception_fires_on_ellipsis_and_docstring_bodies():
    findings = lint("""
        try:
            risky()
        except Exception:
            ...
        try:
            risky()
        except Exception:
            "why would anyone do this"
    """, SUP)
    assert rules_of(findings) == ["swallowed-exception"] * 2


def test_swallowed_exception_quiet_when_handled():
    findings = lint("""
        try:
            risky()
        except OSError as e:
            logger.warning(f"risky failed: {e}")
    """, CKPT)
    assert findings == []


def test_swallowed_exception_suppressed_inline_and_previous_line():
    findings = lint("""
        try:
            risky()
        except OSError:  # dslint: disable=swallowed-exception — benign cleanup
            pass
        try:
            risky()
        # dslint: disable=swallowed-exception — reason on its own line
        except ValueError:
            pass
    """, CKPT)
    assert findings == []


def test_swallowed_exception_out_of_scope_tree():
    findings = lint("try:\n    f()\nexcept OSError:\n    pass\n",
                    "somewhere/else.py")
    assert findings == []


# --------------------------------------------------------- non-atomic-write
def test_non_atomic_write_fires_on_plain_write_modes():
    findings = lint("""
        open(path, "w").write(x)
        with open(path, mode="wb") as f:
            f.write(b)
    """, CKPT)
    assert rules_of(findings) == ["non-atomic-write"] * 2


def test_non_atomic_write_allows_tmp_read_append_and_helpers():
    findings = lint("""
        open(tmp, "w")                 # tmp side of the atomic pattern
        open(path + ".tmp", "wb")
        open(self.tmp_path, "w")
        open(path)                     # read
        open(path, "a")                # append-only journal
        def write_tmp(tmp_path):
            with open(dest, "wb") as f:  # inside the storage helper
                f.write(b)
    """, SUP)
    assert findings == []


def test_non_atomic_write_scoped_to_durability_dirs():
    findings = lint('open(path, "w")\n', OTHER)
    assert findings == []


def test_non_atomic_write_covers_runtime_engine():
    # the engine writes into the checkpoint dir too (recovery script,
    # per-rank shards) — a plain write there races N ranks on shared
    # storage, so runtime/engine.py is explicitly in scope
    bad = lint('open(path, "w")\n', "deepspeed_tpu/runtime/engine.py")
    assert rules_of(bad) == ["non-atomic-write"]
    good = lint('open(path + ".tmp", "w")\n',
                "deepspeed_tpu/runtime/engine.py")
    assert good == []


def test_non_atomic_write_covers_runtime_transport():
    # the fleet transport materializes streamed KV bundle blobs and
    # endpoint announce files other processes read — torn writes there
    # are exactly the corruption the frame digests exist to keep out
    bad = lint('open(npz_path, "wb")\n',
               "deepspeed_tpu/runtime/transport.py")
    assert rules_of(bad) == ["non-atomic-write"]
    good = lint('open(npz_path + ".tmp", "wb")\n',
                "deepspeed_tpu/runtime/transport.py")
    assert good == []


def test_non_atomic_write_suppressible():
    findings = lint(
        'open(p, "wb")  # dslint: disable=non-atomic-write — test scratch\n',
        CKPT)
    assert findings == []


# --------------------------------------------------- unregistered-journal-kind
def test_unregistered_journal_kind_literal():
    findings = lint('self.journal.emit("totally.new", a=1)\n', SUP)
    assert rules_of(findings) == ["unregistered-journal-kind"]
    assert "totally.new" in findings[0].message


def test_unregistered_journal_kind_attribute():
    findings = lint("j.emit(EventKind.NOPE, a=1)\n", OTHER)
    assert rules_of(findings) == ["unregistered-journal-kind"]
    assert "EventKind.NOPE" in findings[0].message


def test_registered_journal_kinds_pass():
    findings = lint("""
        j.emit("rollback", step=1)
        j.emit(EventKind.ROLLBACK, step=1)
        self._emit(EventKind.DATA_BATCH, step=2)
        self._emit(kind, **fields)        # dynamic pass-through wrapper
    """, SUP)
    assert findings == []


def test_journal_kind_rule_skips_the_registry_module_itself():
    findings = lint('j.emit("anything.goes")\n',
                    "deepspeed_tpu/runtime/supervision/events.py")
    assert findings == []


# ---------------------------------------------------- unregistered-fault-point
def test_unregistered_fault_point_qualified_call():
    findings = lint("""
        from deepspeed_tpu.utils import fault_injection
        fault_injection.fire("ckpt.wriet", path=p)
    """, CKPT)
    assert rules_of(findings) == ["unregistered-fault-point"]
    assert "ckpt.wriet" in findings[0].message


def test_unregistered_fault_point_bare_import():
    findings = lint("""
        from deepspeed_tpu.utils.fault_injection import inject
        with inject("bogus.point", fault):
            run()
    """, DATA)
    assert rules_of(findings) == ["unregistered-fault-point"]


def test_registered_fault_points_and_unrelated_fire_pass():
    findings = lint("""
        from deepspeed_tpu.utils import fault_injection
        fault_injection.fire("ckpt.write", path=p)
        fault_injection.fire(point, **ctx)   # dynamic dispatch loop
        gun.fire("bullet")                   # not our registry
    """, CKPT)
    assert findings == []


# -------------------------------------------------------- untimed-collective
def test_untimed_collective_fires():
    findings = lint("""
        def all_gather_base(tensor, group=None):
            return tensor
    """, COMM)
    assert rules_of(findings) == ["untimed-collective"]
    assert "all_gather_base" in findings[0].message


def test_timed_collective_and_non_collectives_pass():
    findings = lint("""
        def all_reduce(tensor, group=None):
            return _timed("all_reduce", lambda: tensor, 0, 1)
        def barrier(group=None):
            with comm_guard("comm.barrier"):
                return None
        def get_rank(group=None):     # introspection: no guard required
            return 0
        def _helper(tensor):          # private: caller owns the guard
            return tensor
    """, COMM)
    assert findings == []


def test_untimed_collective_only_applies_to_comm_module():
    findings = lint("def all_gather_base(t):\n    return t\n",
                    "deepspeed_tpu/comm/collectives.py")
    assert findings == []


# -------------------------------------------------- step-path-nondeterminism
def test_nondeterminism_fires_on_wall_clock_and_global_rng():
    findings = lint("""
        import time, random
        import numpy as np
        t = time.time()
        random.shuffle(xs)
        np.random.shuffle(x)
    """, DATA)
    assert rules_of(findings) == ["step-path-nondeterminism"] * 3
    assert [f.line for f in findings] == [4, 5, 6]


def test_nondeterminism_allows_seeded_generators():
    findings = lint("""
        import random
        import numpy as np
        rng = np.random.default_rng(seed + epoch)
        r = random.Random(7)
    """, DATA)
    assert findings == []


def test_nondeterminism_covers_verify_replay_but_not_other_scripts():
    bad = "import time\nt = time.time()\n"
    assert rules_of(lint(bad, "scripts/verify_replay.py")) == \
        ["step-path-nondeterminism"]
    assert lint(bad, "scripts/dump_run_events.py") == []


# ---------------------------------------------------------- jit-in-hot-path
def test_jit_in_hot_path_fires_on_uncached_forms():
    findings = lint("""
        def per_call(self, x):
            f = jax.jit(fn)                 # local binding: fresh per call
            y = jax.jit(fn)(x)              # immediately invoked
            return jax.jit(fn)              # escapes uncached
    """, INF)
    assert rules_of(findings) == ["jit-in-hot-path"] * 3
    assert "per_call" in findings[0].message


def test_jit_in_hot_path_fires_on_decorator_inside_function():
    findings = lint("""
        def factory(cfg):
            @jax.jit
            def run(x):
                return x
            return run
    """, OTHER)
    assert rules_of(findings) == ["jit-in-hot-path"]
    assert "'run'" in findings[0].message and "factory" in findings[0].message


def test_jit_in_hot_path_allows_cached_forms():
    findings = lint("""
        FWD = jax.jit(fn)                       # module scope

        @jax.jit                                # module-scope decorator
        def top(x):
            return x

        _CACHED = None

        def lazily():
            global _CACHED
            if _CACHED is None:
                _CACHED = jax.jit(fn)           # global-cached
            return _CACHED

        class E:
            def __init__(self):
                self._fwd_jit = jax.jit(fn)     # attribute
                self._p = {"tick": jax.jit(fn)} # dict literal on attribute
            def build(self, sig):
                self._p[sig] = jax.jit(fn)      # keyed program dict
            def register(self, reg):
                self._f = reg.register("f", jax.jit(fn))  # wrapped+cached
    """, INF)
    assert findings == []


def test_jit_in_hot_path_scope_excludes_benchmarks_and_scripts():
    bad = "def f(x):\n    return jax.jit(g)(x)\n"
    assert lint(bad, "deepspeed_tpu/benchmarks/inference/fixture.py") == []
    assert lint(bad, "scripts/fixture.py") == []
    assert rules_of(lint(bad, OTHER)) == ["jit-in-hot-path"]


def test_jit_in_hot_path_suppressible():
    findings = lint("""
        def one_shot(rng):
            # dslint: disable=jit-in-hot-path — init-time materialization
            return jax.jit(init_fn)(rng)
    """, OTHER)
    assert findings == []


# ---------------------------------------------------- unbucketed-static-arg
def test_unbucketed_static_arg_fires_on_raw_sig_and_subscript():
    findings = lint("""
        class S:
            def generate(self, max_new_tokens):
                sig = (max_new_tokens, True)
                return self._progs[sig]
            def lookup(self, max_len):
                return self._progs[max_len]
    """, INF)
    assert rules_of(findings) == ["unbucketed-static-arg"] * 2
    assert "'max_new_tokens'" in findings[0].message
    assert "'max_len'" in findings[1].message


def test_unbucketed_static_arg_fires_on_config_attribute_key():
    findings = lint("""
        def admit(self, config):
            return self._progs[config.max_len]
    """, SERVE)
    assert rules_of(findings) == ["unbucketed-static-arg"]


def test_unbucketed_static_arg_allows_helper_routing_and_slices():
    findings = lint("""
        def generate(self, max_new_tokens, max_len):
            n = bucket_max_new_tokens(max_new_tokens)   # sanitized rebind
            max_len = bucket_cache_len(max_len, 128)    # self-rebind
            sig = (n, max_len, True)
            out = self._progs[sig](x)
            key = self._p[bucket_max_new_tokens(max_new_tokens)]  # at use
            return out[:, :max_new_tokens]              # array slice: fine
    """, INF)
    assert findings == []


def test_unbucketed_static_arg_scoped_to_inference_and_serving():
    bad = "def f(self, max_len):\n    return self._p[max_len]\n"
    assert lint(bad, OTHER) == []
    assert rules_of(lint(bad, SERVE)) == ["unbucketed-static-arg"]


def test_unbucketed_static_arg_suppressible():
    findings = lint("""
        def gen(self, max_new_tokens):
            # dslint: disable=unbucketed-static-arg — deliberate per-budget
            sig = (max_new_tokens,)
            return self._p[sig]
    """, INF)
    assert findings == []


# --------------------------------------------------- host-sync-in-hot-path
def test_host_sync_fires_inside_hot_path():
    findings = lint("""
        @hot_path
        def tick(self):
            toks = np.asarray(nxt)
            s = jax.device_get(scale)
            f = float(norm)
            i = loss.item()
    """, SERVE)
    assert rules_of(findings) == ["host-sync-in-hot-path"] * 4
    assert "'np.asarray'" in findings[0].message
    assert "tick" in findings[0].message


def test_host_sync_quiet_outside_hot_path_and_on_device_ops():
    findings = lint("""
        def not_hot(self):
            return np.asarray(x)        # unmarked function: fine
        @hot_path
        def tick(self):
            a = jnp.asarray(x)          # device-side: fine
            n = float(1.0)              # literal: no device pull
            return a
    """, OTHER)
    assert findings == []


def test_host_sync_suppressible_with_reason():
    findings = lint("""
        @hot_path
        def tick(self):
            self.registry.note_host_sync("serving.tick")
            # dslint: disable=host-sync-in-hot-path — output boundary
            return np.asarray(nxt)
    """, SERVE)
    assert findings == []


# -------------------------------------------------------- missing-donation
def test_missing_donation_fires_on_state_sized_programs():
    findings = lint("""
        J = jax.jit(lambda params, batch: params)

        def apply_core(params, master, opt_state, grad_acc, hyper):
            return params

        class E:
            def build(self):
                self._apply_jit = jax.jit(apply_core)
    """, OTHER)
    assert rules_of(findings) == ["missing-donation"] * 2
    assert "params" in findings[0].message
    assert "apply_core" in findings[1].message


def test_missing_donation_allows_donating_and_benign_programs():
    findings = lint("""
        def micro(params, grad_acc, batch):
            return grad_acc

        class E:
            def build(self):
                self._micro_jit = jax.jit(micro, donate_argnums=(1,))
                self._take = jax.jit(lambda lg, i: lg[i])   # small args
                self._eval = jax.jit(self.module.loss_fn)   # unresolvable
    """, OTHER)
    assert findings == []


def test_missing_donation_scoped_to_runtime():
    bad = "J = jax.jit(lambda params: params)\n"
    assert lint(bad, INF) == []
    assert rules_of(lint(bad, OTHER)) == ["missing-donation"]


def test_missing_donation_suppressible():
    findings = lint("""
        class E:
            def build(self):
                # dslint: disable=missing-donation — read-only stats pass
                self._stats = jax.jit(lambda grad_acc: grad_acc.sum())
    """, OTHER)
    assert findings == []


# ----------------------------------------------------- framework behaviors
def test_parse_error_is_a_finding_not_a_crash():
    findings = lint("def broken(:\n", DATA)
    assert rules_of(findings) == ["parse-error"]


def test_findings_sorted_and_render_format():
    findings = lint("""
        import time
        random.shuffle(xs)
        t = time.time()
    """, DATA)
    assert [f.line for f in findings] == sorted(f.line for f in findings)
    r = findings[0].render()
    assert r.startswith(f"{DATA}:3: step-path-nondeterminism")


# ------------------------------------------------- unregistered-telemetry-name
TEL_PROJECT = Project(
    event_kind_map={"ROLLBACK": "rollback"},
    fault_points=set(),
    bucketing_helpers=set(),
    span_name_map={"TRAIN_FWD": "train.fwd", "SERVE_TICK": "serve.tick"},
    metric_name_map={"MFU": "train.mfu", "STEP_TIME_S": "train.step_time_s"},
)


def tlint(src, relpath=OTHER):
    return lint_source(textwrap.dedent(src), relpath, TEL_PROJECT)


def test_telemetry_name_fires_on_unregistered_span_literal():
    findings = tlint("""
        with tracer.span("train.mystery"):
            work()
    """)
    assert rules_of(findings) == ["unregistered-telemetry-name"]
    assert "train.mystery" in findings[0].message


def test_telemetry_name_fires_on_unknown_spanname_attr():
    findings = tlint("""
        with self.tracer.span(SpanName.TRAIN_MYSTERY):
            work()
    """)
    assert rules_of(findings) == ["unregistered-telemetry-name"]


def test_telemetry_name_checks_recorded_spans_like_opened_ones():
    findings = tlint("""
        tracer.record("serve.mystery", t0, dur, rid=rid)
        self.tracer.record(SpanName.SERVE_MYSTERY, t0, dur)
        tracer.record(SpanName.SERVE_TICK, t0, dur)
        tuner.record(candidate, value)   # another record(): uninspected
    """)
    assert rules_of(findings) == ["unregistered-telemetry-name"] * 2


def test_telemetry_name_fires_on_unregistered_metric():
    findings = tlint("""
        reg.gauge("train.bogus").set(1.0)
        reg.histogram(MetricName.BOGUS).observe(2.0)
    """)
    assert rules_of(findings) == ["unregistered-telemetry-name"] * 2


def test_telemetry_name_quiet_on_registered_names_and_dynamic():
    findings = tlint("""
        with tracer.span("train.fwd"):
            reg.gauge("train.mfu").set(0.4)
        with tracer.span(SpanName.SERVE_TICK):
            reg.histogram(MetricName.STEP_TIME_S).observe(0.1)
        tracer.span(name_variable)       # dynamic: passes uninspected
        soup.span  # bare attribute, not a call
    """)
    assert findings == []


def test_telemetry_name_skips_the_registry_modules_and_suppresses():
    bad = 'tracer.span("nope")\n'
    assert tlint(bad, "deepspeed_tpu/telemetry/spans.py") == []
    assert tlint(bad, "deepspeed_tpu/telemetry/metrics.py") == []
    findings = tlint("""
        # dslint: disable=unregistered-telemetry-name — fixture
        tracer.span("nope")
    """)
    assert findings == []


# -------------------------------------------------------- untraced-fleet-event
FLEET_PROJECT = Project(
    event_kind_map={"SERVE_FLEET_SPAWN": "serve.fleet.spawn",
                    "SERVE_FLEET_DEGRADED": "serve.fleet.degraded",
                    "FLEET_RESTART": "fleet.restart",
                    "FLEET_SPAWN": "fleet.spawn",
                    "SERVE_REQUEST": "serve.request",
                    "DATA_BATCH": "data.batch"},
    fault_points=set(),
    bucketing_helpers=set(),
)


def flint(src, relpath=SERVE):
    return lint_source(textwrap.dedent(src), relpath, FLEET_PROJECT)


def test_untraced_fleet_event_fires_on_literal_and_attribute_kinds():
    findings = flint("""
        journal.emit("serve.fleet.spawn", role="prefill", worker=1)
        self._emit(EventKind.FLEET_RESTART, incarnation=2)
    """)
    assert rules_of(findings) == ["untraced-fleet-event"] * 2
    assert "trace" in findings[0].message


def test_untraced_fleet_event_quiet_with_trace_kwarg_even_none():
    findings = flint("""
        journal.emit("serve.fleet.spawn", worker=1, trace=ctx.fields())
        journal.emit(EventKind.SERVE_FLEET_DEGRADED, trace=None)
    """)
    assert findings == []


def test_untraced_fleet_event_ignores_non_fleet_kinds():
    findings = flint("""
        journal.emit("serve.request", request_id="r")
        journal.emit(EventKind.DATA_BATCH, step=1)
        journal.emit(kind_variable, step=1)   # dynamic: passes uninspected
        emit("serve.fleet.spawn")             # bare call, not a method
    """)
    assert findings == []


def test_untraced_fleet_event_scoped_and_suppressible():
    bad = 'journal.emit("fleet.spawn", pids=[1])\n'
    assert flint(bad, "tests/unit/fixture.py") == []
    findings = flint("""
        # dslint: disable=untraced-fleet-event — fixture without context
        journal.emit("fleet.spawn", pids=[1])
    """)
    assert findings == []


# --------------------------------------------------- unguarded-shared-state
def test_unguarded_shared_state_fires_on_cross_thread_write():
    findings = lint("""
        import threading

        class Pump:
            def __init__(self):
                self.count = 0
                self._lock = TrackedLock(LockName.SERVE_METRICS)
                self._t = threading.Thread(target=self._run, name="p",
                                           daemon=True)

            def _run(self):
                self.count += 1

            def snapshot(self):
                return self.count

            def stop(self):
                self._t.join(timeout=1.0)
    """, SERVE)
    assert rules_of(findings) == ["unguarded-shared-state"]
    assert "count" in findings[0].message


def test_unguarded_shared_state_quiet_when_guarded_or_set_once():
    findings = lint("""
        import threading

        class Pump:
            def __init__(self):
                self.count = 0
                self.config = "set once before start()"
                self._lock = TrackedLock(LockName.SERVE_METRICS)
                self._stop = threading.Event()
                self._t = threading.Thread(target=self._run, name="p",
                                           daemon=True)

            def _run(self):
                with self._lock:
                    self.count += 1
                self._stop.set()

            def snapshot(self):
                with self._lock:
                    return self.count

            def stop(self):
                self._t.join(timeout=1.0)
    """, SERVE)
    assert findings == []


def test_unguarded_shared_state_ignores_threadless_classes_and_suppression():
    assert lint("""
        class Plain:
            def bump(self):
                self.count += 1
    """, SERVE) == []
    findings = lint("""
        import threading

        class Pump:
            def __init__(self):
                self._t = threading.Thread(target=self._run, name="p",
                                           daemon=True)

            def _run(self):
                # dslint: disable=unguarded-shared-state — single writer, reader tolerates staleness
                self.count = 1

            def read(self):
                return 0

            def stop(self):
                self._t.join(timeout=1.0)
    """, SERVE)
    assert findings == []


# ------------------------------------------------------- blocking-under-lock
def test_blocking_under_lock_fires_on_sleep_subprocess_and_join():
    findings = lint("""
        import subprocess
        import time

        class W:
            def __init__(self):
                self._lock = TrackedLock(LockName.SERVE_METRICS)

            def a(self):
                with self._lock:
                    time.sleep(0.5)

            def b(self):
                with self._lock:
                    subprocess.run(["ls"])

            def c(self, worker):
                with self._lock:
                    worker.join(timeout=2.0)
    """, SERVE)
    assert rules_of(findings) == ["blocking-under-lock"] * 3


def test_blocking_under_lock_quiet_outside_lock_and_for_cond_wait():
    findings = lint("""
        import time

        class W:
            def __init__(self):
                self._cond = threading.Condition(
                    TrackedRLock(LockName.SERVE_GATEWAY))

            def a(self):
                time.sleep(0.5)
                with self._cond:
                    self._cond.wait(timeout=1.0)

            def b(self, path):
                with self._cond:
                    with open(path, "a") as f:
                        f.write("append-mode audit line")
    """, SERVE)
    assert findings == []


def test_blocking_under_lock_suppressible():
    findings = lint("""
        import time

        class W:
            def __init__(self):
                self._lock = TrackedLock(LockName.SERVE_METRICS)

            def a(self):
                with self._lock:
                    # dslint: disable=blocking-under-lock — test-only fixture pacing
                    time.sleep(0.01)
    """, SERVE)
    assert findings == []


# ---------------------------------------------------------------- lock-order
def test_lock_order_fires_on_bare_primitive_and_unregistered_name():
    findings = lint("""
        import threading

        class W:
            def __init__(self):
                self._a = threading.Lock()
                self._b = TrackedLock("not.in.the.registry")
    """, SERVE)
    assert sorted(rules_of(findings)) == ["lock-order"] * 2


def test_lock_order_fires_on_rank_inversion_and_quiet_in_order():
    findings = lint("""
        class W:
            def __init__(self):
                self._outer = TrackedLock(LockName.SERVE_GATEWAY)
                self._inner = TrackedLock(LockName.SERVE_METRICS)

            def bad(self):
                with self._inner:
                    with self._outer:
                        pass

            def good(self):
                with self._outer:
                    with self._inner:
                        pass
    """, SERVE)
    assert rules_of(findings) == ["lock-order"]
    assert "serve.gateway" in findings[0].message
    assert "serve.metrics" in findings[0].message


def test_lock_order_multi_item_with_and_condition_wrapping():
    findings = lint("""
        class W:
            def __init__(self):
                self._outer = TrackedLock(LockName.SERVE_GATEWAY)
                self._inner = TrackedLock(LockName.SERVE_METRICS)
                self._cond = threading.Condition(
                    TrackedRLock(LockName.SERVE_GATEWAY))

            def bad(self):
                with self._inner, self._outer:
                    pass
    """, SERVE)
    assert rules_of(findings) == ["lock-order"]


def test_lock_order_suppressible():
    findings = lint("""
        import threading

        class W:
            def __init__(self):
                # dslint: disable=lock-order — scratch lock in a test fixture
                self._a = threading.Lock()
    """, SERVE)
    assert findings == []


# --------------------------------------------------------- thread-discipline
def test_thread_discipline_fires_on_anonymous_daemonless_joinless():
    findings = lint("""
        import threading

        class W:
            def start(self):
                self._t = threading.Thread(target=self._run)
                self._t.start()
    """, SERVE)
    assert sorted(set(rules_of(findings))) == ["thread-discipline"]
    msgs = " ".join(f.message for f in findings)
    assert "name=" in msgs and "daemon=" in msgs and "join" in msgs


def test_thread_discipline_quiet_on_named_daemon_joined():
    findings = lint("""
        import threading

        class W:
            def start(self):
                self._t = threading.Thread(target=self._run, name="w",
                                           daemon=True)
                self._t.start()

            def stop(self, timeout=1.0):
                self._t.join(timeout=timeout)
    """, SERVE)
    assert findings == []


def test_thread_discipline_str_join_is_not_a_thread_join():
    findings = lint("""
        import threading

        class W:
            def start(self):
                self._t = threading.Thread(target=self._run, name="w",
                                           daemon=True)

            def render(self, parts):
                return ", ".join(parts)
    """, SERVE)
    assert any("join" in f.message for f in findings)
    assert rules_of(findings) == ["thread-discipline"]


# ----------------------------------------------------- signal-handler-purity
def test_signal_handler_purity_fires_on_lock_sleep_and_jax():
    findings = lint("""
        import signal
        import time

        def _handler(signum, frame):
            with state._lock:
                state.flag = True
            time.sleep(1.0)
            jax.block_until_ready(x)

        signal.signal(signal.SIGTERM, _handler)
    """, SERVE)
    assert rules_of(findings) == ["signal-handler-purity"] * 3


def test_signal_handler_purity_quiet_on_flags_and_journal():
    findings = lint("""
        import signal

        def _handler(signum, frame):
            state.preempt_requested = True
            journal.emit("rollback", signum=signum)

        signal.signal(signal.SIGTERM, _handler)
    """, SERVE)
    assert findings == []


def test_signal_handler_purity_only_checks_registered_handlers():
    findings = lint("""
        import time

        def not_a_handler(signum, frame):
            time.sleep(1.0)
    """, SERVE)
    assert findings == []


def test_signal_handler_purity_suppressible():
    findings = lint("""
        import signal

        def _handler(signum, frame):
            # dslint: disable=signal-handler-purity — teardown path, exits right after
            proc.wait(timeout=5)

        signal.signal(signal.SIGTERM, _handler)
    """, SERVE)
    assert findings == []
