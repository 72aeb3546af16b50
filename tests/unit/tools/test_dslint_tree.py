"""The tier-1 gate: dslint over the real tree must be clean against the
committed baseline, the parsed registries must match what the subsystems
actually ship, and the drift checks must catch registry/docs skew.  This is
the test that fails when someone introduces an unregistered journal kind,
an un-``_timed`` collective, a swallowed exception, or a non-atomic
durability write."""

import importlib.util
import os
import subprocess
import sys

import pytest

from tools.dslint import (BASELINE_PATH, Project, diff_against_baseline,
                          format_baseline, lint_source, lint_tree,
                          load_baseline)
from tools.dslint.project_checks import run_project_checks

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


@pytest.fixture(scope="module")
def tree_findings():
    """One full-tree lint shared by every whole-tree assertion in this
    module — a full pass costs ~9s, so each test re-running it would
    dominate the tier-1 budget."""
    return lint_tree(REPO)


def test_tree_is_clean_against_baseline(tree_findings):
    baseline = load_baseline(os.path.join(REPO, BASELINE_PATH))
    new, _stale = diff_against_baseline(tree_findings, baseline)
    assert new == [], "new dslint findings (fix or suppress with a " \
        "reason; do NOT baseline new code):\n" + \
        "\n".join(f.render() for f in new)


def test_baseline_has_no_stale_entries(tree_findings):
    baseline = load_baseline(os.path.join(REPO, BASELINE_PATH))
    _new, stale = diff_against_baseline(tree_findings, baseline)
    assert stale == 0, (f"{stale} baseline entr(y/ies) no longer match any "
                        "finding — the violations were fixed; delete the "
                        "lines (burn-down) so they can't mask new ones")


def test_registries_parse_from_the_real_modules():
    p = Project(REPO)
    assert "rollback" in p.event_kinds
    assert "data.batch" in p.event_kinds
    assert len(p.event_kinds) >= 13
    assert {"ckpt.write", "comm.barrier", "data.next"} <= p.fault_points
    # every registered kind has a dump_run_events summary entry
    assert p.event_kind_names <= p.summary_field_names | p.event_kinds
    assert p.abort_kind_names <= p.event_kind_names


def test_unregistered_journal_kind_is_caught_against_real_registry():
    findings = lint_source('j.emit("my.new.kind", step=1)\n',
                           "deepspeed_tpu/runtime/supervision/x.py",
                           Project(REPO))
    assert [f.rule for f in findings] == ["unregistered-journal-kind"]


def test_untimed_collective_is_caught_on_the_real_comm_module():
    # bypass _timed in the real comm.py source: every public collective
    # must light up
    with open(os.path.join(REPO, "deepspeed_tpu/comm/comm.py")) as f:
        src = f.read().replace("_timed(", "_untimed(")
    findings = lint_source(src, "deepspeed_tpu/comm/comm.py", Project(REPO))
    names = {f.message.split("'")[1] for f in findings
             if f.rule == "untimed-collective"}
    assert {"barrier", "all_reduce", "all_gather", "reduce_scatter",
            "broadcast", "all_to_all_single"} <= names


def test_bucketing_registry_parses_from_the_real_module():
    p = Project(REPO)
    assert {"bucket_max_new_tokens", "bucket_cache_len",
            "tile_cache_len"} <= p.bucketing_helpers


def test_jit_in_hot_path_caught_on_the_real_batcher_module():
    # un-cache the batcher's program dict in the real source: every jit in
    # it becomes a fresh-compile-per-call and must light up
    with open(os.path.join(REPO, "deepspeed_tpu/serving/batcher.py")) as f:
        src = f.read().replace("self._p = self.registry.register_all({",
                               "programs = ({")
    findings = lint_source(src, "deepspeed_tpu/serving/batcher.py",
                           Project(REPO))
    # the dict's jit sites: prefill / extend at both widths, release, tick,
    # and the ONE site that builds the four admission programs
    assert sum(1 for f in findings if f.rule == "jit-in-hot-path") == 7


def test_host_sync_caught_when_real_tick_suppression_removed():
    with open(os.path.join(REPO, "deepspeed_tpu/serving/batcher.py")) as f:
        src = f.read().replace(
            "# dslint: disable=host-sync-in-hot-path — one d2h pull per "
            "tick", "#")
    findings = lint_source(src, "deepspeed_tpu/serving/batcher.py",
                           Project(REPO))
    # the one pull every tick variant shares (tokens, or a speculative
    # round's window and counts in one transfer)
    assert [f.rule for f in findings] == ["host-sync-in-hot-path"]
    assert "jax.device_get" in findings[0].message \
        and "'pull'" in findings[0].message


def test_lock_registry_parses_from_the_real_module():
    p = Project(REPO)
    assert p.lock_name_map["SERVE_GATEWAY"] == "serve.gateway"
    assert p.lock_name_map["JOURNAL_EMIT"] == "journal.emit"
    assert len(p.lock_order) >= 15
    assert set(p.lock_order) == p.lock_names
    # journal.emit is innermost: everything journals, nothing is
    # acquired while journaling
    assert p.lock_order[-1] == "journal.emit"


def test_lock_order_fires_when_real_gateway_lock_untracked():
    # un-track the gateway's scheduler condition in the real source: the
    # watchdog goes blind to the busiest lock in the serving tier
    with open(os.path.join(REPO, "deepspeed_tpu/serving/gateway.py")) as f:
        src = f.read().replace(
            "threading.Condition(TrackedRLock(LockName.SERVE_GATEWAY))",
            "threading.Condition()")
    findings = lint_source(src, "deepspeed_tpu/serving/gateway.py",
                           Project(REPO))
    assert [f.rule for f in findings] == ["lock-order"]
    assert "bare threading.Condition()" in findings[0].message


def test_lock_order_fires_on_reversed_nesting_against_real_registry():
    # scratch copy of the real gateway module with one inverted nesting
    # appended — the rank check must resolve both names through the real
    # LOCK_ORDER (serve.gateway outranks serve.metrics)
    with open(os.path.join(REPO, "deepspeed_tpu/serving/gateway.py")) as f:
        src = f.read()
    src += (
        "\n\nclass _ScratchInversion:\n"
        "    def __init__(self):\n"
        "        self._outer = TrackedLock(LockName.SERVE_GATEWAY)\n"
        "        self._inner = TrackedLock(LockName.SERVE_METRICS)\n"
        "\n"
        "    def inverted(self):\n"
        "        with self._inner:\n"
        "            with self._outer:\n"
        "                pass\n")
    findings = lint_source(src, "deepspeed_tpu/serving/gateway.py",
                           Project(REPO))
    assert [f.rule for f in findings] == ["lock-order"]
    assert "violates LOCK_ORDER" in findings[0].message
    assert "serve.gateway" in findings[0].message


def test_drift_check_catches_removed_registry_kind():
    p = Project(REPO)
    del p.event_kind_map["ROLLBACK"]
    findings = run_project_checks(REPO, p)
    # the docs still document 'rollback' → drift both ways
    assert any(f.rule == "event-kind-drift" and "'rollback'" in f.message
               for f in findings)


def test_drift_check_catches_undocumented_new_kind():
    p = Project(REPO)
    p.event_kind_map["BRAND_NEW"] = "brand.new"
    msgs = [f.message for f in run_project_checks(REPO, p)
            if f.rule == "event-kind-drift"]
    assert any("no SUMMARY_FIELDS entry" in m for m in msgs)
    assert any("documented in neither" in m for m in msgs)


def test_drift_checks_pass_on_the_real_tree():
    assert run_project_checks(REPO, Project(REPO)) == []


# ------------------------------------------------------------------- CLI
@pytest.fixture(scope="module")
def cli():
    spec = importlib.util.spec_from_file_location(
        "dslint_cli", os.path.join(REPO, "scripts", "dslint.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_cli_exits_zero_on_clean_tree(cli, capsys):
    # whole-tree cleanliness is proven by test_tree_is_clean_against_baseline
    # plus the CLI==library byte-identity check below; this run covers the
    # CLI's default-baseline wiring on the subtree that carries every
    # baselined finding, without a third ~9s full-tree pass
    assert cli.main(["deepspeed_tpu/runtime"]) == 0
    assert "0 new" in capsys.readouterr().err


def test_cli_exits_nonzero_when_baseline_missing_entries(cli, tmp_path,
                                                         capsys):
    # every baselined finding lives under runtime/, so the subtree run is
    # enough to prove an empty baseline fails (and much cheaper than a
    # whole-tree pass)
    empty = tmp_path / "empty_baseline.txt"
    empty.write_text("# no grandfathered findings\n")
    assert cli.main(["--baseline", str(empty),
                     "deepspeed_tpu/runtime"]) == 1
    out = capsys.readouterr()
    assert "swallowed-exception" in out.out


def test_cli_update_baseline_is_deterministic(cli, tmp_path, tree_findings):
    b1 = tmp_path / "b1.txt"
    assert cli.main(["--update-baseline", "--baseline", str(b1)]) == 0
    # the CLI's own lint pass and this module's cached library pass are
    # two independent lints of the same tree — byte-identical output IS
    # the determinism claim
    assert b1.read_text() == format_baseline(tree_findings)
    # a regenerated baseline is immediately clean and sorted
    new, stale = diff_against_baseline(tree_findings,
                                       load_baseline(str(b1)))
    assert new == [] and stale == 0
    keys = [l for l in b1.read_text().splitlines()
            if l and not l.startswith("#")]
    assert keys == sorted(keys)
    # and semantically identical to the committed one
    committed = load_baseline(os.path.join(REPO, BASELINE_PATH))
    assert load_baseline(str(b1)) == committed


def test_cli_path_filter_restricts_scope(cli, capsys):
    # the comm subtree is clean even with no baseline at all
    assert cli.main(["--no-baseline", "deepspeed_tpu/comm"]) == 0


def test_cli_jobs_matches_serial_output(cli, capsys):
    # parallel parsing must not change findings or exit status; the
    # runtime/ subtree carries all 12 baselined findings, so this
    # exercises worker-side rule evaluation AND baseline matching
    assert cli.main(["--jobs", "2", "deepspeed_tpu/runtime"]) == 0
    err = capsys.readouterr().err
    assert "0 new" in err and "12 baselined" in err


def test_cli_changed_mode_is_clean(cli, capsys):
    # the working tree is clean vs baseline, so any git-derived subset of
    # it is too (an empty changed set exits 0 with a note)
    assert cli.main(["--changed"]) == 0
    err = capsys.readouterr().err
    assert "0 new" in err or "no changed" in err


def test_cli_changed_rejects_update_baseline(cli, capsys):
    assert cli.main(["--changed", "--update-baseline"]) == 2


def test_cli_runs_standalone_without_jax():
    """The linter must work as a bare subprocess (pre-commit / CI) with no
    jax and no deepspeed_tpu import."""
    # the runtime/ subtree is enough to prove standalone operation (the
    # whole-tree pass is covered in-process above) and keeps this cheap
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "dslint.py"),
         "deepspeed_tpu/runtime"],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stdout + r.stderr
    assert "0 new" in r.stderr


def test_baseline_format_round_trip(tree_findings):
    from collections import Counter
    current = Counter(f.key for f in tree_findings)
    # the committed baseline covers exactly the current findings
    assert load_baseline(os.path.join(REPO, BASELINE_PATH)) == current
    # and format/load round-trips
    loaded = Counter()
    for line in format_baseline(tree_findings).splitlines():
        if line and not line.startswith("#"):
            loaded[line] += 1
    assert loaded == current
