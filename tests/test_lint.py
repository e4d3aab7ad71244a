"""`pio lint` static-analysis pass (ISSUE 10 acceptance).

- THE consolidated guard: the whole repo is lint-clean under every
  rule (the six PR 3-9 scattered AST guards now route through this
  same engine — see the thin `assert_rule_clean` tests left in their
  original modules for coverage parity).
- every rule is proven LIVE by a seeded-violation test: a tmp package
  tree carrying exactly one defect, and the exact finding the rule
  emits for it (a rule that silently stopped matching would fail
  here, not in review).
- guard-migration guard: re-introducing a known historical violation
  into a COPY of the real event_server.py re-surfaces the original
  finding — the consolidation kept coverage, not just test names.
- suppression semantics: per-line disable honoured, unused disables
  are findings, and the repo's suppression inventory is asserted so
  it can only shrink deliberately.
- regression tests for the defects the new rules surfaced (Lease
  fd race → clean fence, ingest shed-map lock, admission-counter lock
  discipline under thread contention).
- `pio lint` CLI: rc 0/1, --json shape, --rule filter, --list-rules,
  and a subprocess proof that the console lint path never imports jax
  (the sub-10s tier-1 budget depends on it).
"""

from __future__ import annotations

import ast
import json
import pathlib
import subprocess
import sys
import textwrap
import threading

import pytest

import incubator_predictionio_tpu
from incubator_predictionio_tpu.tools import lint as pio_lint
from incubator_predictionio_tpu.tools.lint import (ALL_RULES, Project,
                                                   run_lint)
from incubator_predictionio_tpu.tools.lint.cli import main as lint_cli

pytestmark = pytest.mark.lint

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parent
PKG = pathlib.Path(incubator_predictionio_tpu.__file__).parent


# ---------------------------------------------------------------------------
# the consolidated guard: the repo itself
# ---------------------------------------------------------------------------

def test_repo_is_lint_clean():
    """Every rule, the whole package, zero findings — this single test
    IS the enforcement the six scattered guard tests used to share
    between them (they still exist as thin per-rule calls for
    per-subsystem attribution)."""
    result = pio_lint.lint_repo()
    assert not result["findings"], "\n".join(
        f.render() for f in result["findings"])
    assert len(result["rules"]) >= 8


def test_suppression_inventory_can_only_shrink():
    """The repo's inline `# pio-lint: disable=` inventory. Additions
    are a deliberate act: every new entry needs a reason string in the
    source AND a row here."""
    result = pio_lint.lint_repo()
    inventory = [(s.path, s.line, s.rules, s.reason) for s in
                 result["suppressions"]]
    assert inventory == [
        # gang identity knobs (rank / world size) parse STRICTLY: a
        # garbled value must crash the worker at startup, not fall back
        # to rank 0 / world 1 and corrupt the gang topology
        ("incubator_predictionio_tpu/parallel/distributed.py", 89,
         ("knob-envknobs",),
         "identity knob: strict crash beats tolerant world=1"),
        ("incubator_predictionio_tpu/parallel/distributed.py", 91,
         ("knob-envknobs",),
         "identity knob: strict crash beats tolerant rank=0"),
    ], (
        "the pio-lint suppression inventory changed — if intentional, "
        f"update this test with the reasons: {inventory}")


def test_rule_target_modules_exist():
    """The confinement rules name their chokepoint modules; if one is
    renamed the rule must not become vacuously green."""
    p = Project.from_repo()
    for rel in ("data/api/event_server.py", "data/api/event_log.py",
                "data/api/ingest_wal.py", "data/api/ingest_buffer.py",
                "workflow/create_server.py", "workflow/model_artifact.py",
                "parallel/supervisor.py", "data/storage/http_backend.py",
                "common/envknobs.py"):
        assert p.module(rel) is not None, rel


def test_all_rules_in_docs_catalog():
    """docs/operations.md 'Static analysis' lists every active rule."""
    ops = (REPO / "docs" / "operations.md").read_text()
    for rule in ALL_RULES:
        assert f"`{rule.name}`" in ops, rule.name
    assert "`unused-suppression`" in ops and "`parse-error`" in ops


def test_lint_marker_registered():
    assert '"lint: ' in (REPO / "pyproject.toml").read_text()


# ---------------------------------------------------------------------------
# seeded-violation harness
# ---------------------------------------------------------------------------

def make_project(tmp_path, files: dict, docs: dict | None = None) -> Project:
    pkg = tmp_path / "incubator_predictionio_tpu"
    for rel, src in files.items():
        p = pkg / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    docs_dir = tmp_path / "docs"
    docs_dir.mkdir(exist_ok=True)
    for name, text in (docs or {}).items():
        (docs_dir / name).write_text(textwrap.dedent(text))
    return Project(tmp_path)


def findings_for(tmp_path, files, rules, docs=None):
    result = run_lint(make_project(tmp_path, files, docs), ALL_RULES,
                      only=rules)
    return result["findings"]


# ---------------------------------------------------------------------------
# seeded violations: one per rule, asserting the exact finding
# ---------------------------------------------------------------------------

def test_seeded_ingest_hot_path(tmp_path):
    fs = findings_for(tmp_path, {"data/api/event_server.py": """
        class EventServer:
            async def handle_create(self, request):
                self.storage.get_l_events().insert(1, 2)
            async def handle_batch(self, request):
                await self.ingest.ingest_events([])
            async def handle_webhook(self, request):
                await self.ingest.ingest_events([])
        """}, ["ingest-hot-path"])
    assert len(fs) == 2  # direct insert + no .ingest use in handle_create
    assert fs[0].rule == "ingest-hot-path"
    assert any("`.insert(`" in f.message for f in fs)
    assert any("does not feed the ingest buffer" in f.message for f in fs)
    assert fs[0].path.endswith("data/api/event_server.py")


def test_seeded_hot_handler_rename_is_caught(tmp_path):
    """The legacy test asserted seen == hot; the rule keeps that."""
    fs = findings_for(tmp_path, {"data/api/event_server.py": """
        class EventServer:
            async def handle_create(self, request):
                await self.ingest.ingest_events([])
        """}, ["ingest-hot-path"])
    assert sorted(f.message for f in fs) == [
        "hot handler handle_batch not found on EventServer — renaming "
        "it silently drops the guard",
        "hot handler handle_webhook not found on EventServer — renaming "
        "it silently drops the guard"]


def test_seeded_spawn_confinement(tmp_path):
    fs = findings_for(tmp_path, {
        "workflow/helper.py": """
            import subprocess
            def go():
                subprocess.Popen(["x"])
            """,
        "parallel/supervisor.py": """
            import subprocess
            def spawn():
                return subprocess.Popen(["worker"])  # the ONE legal site
            """,
    }, ["spawn-confinement"])
    assert [(f.line, f.rule) for f in fs] == [(4, "spawn-confinement")]
    assert "subprocess.Popen() outside parallel/supervisor.py" \
        in fs[0].message


def test_seeded_resilient_urlopen(tmp_path):
    fs = findings_for(tmp_path, {
        "data/storage/custom.py": """
            import urllib.request
            def fetch(url):
                return urllib.request.urlopen(url)
            """,
        "data/storage/http_backend.py": """
            import urllib.request
            class _Transport:
                def call(self, req):
                    return urllib.request.urlopen(req)  # the legal home
            """,
    }, ["resilient-urlopen"])
    assert [(f.path.endswith("custom.py"), f.line) for f in fs] == [(True, 4)]


def test_seeded_wal_suffix_confinement(tmp_path):
    fs = findings_for(tmp_path, {
        "data/api/sidecar.py": 'SEG = "0001.wal"\n',
        "data/api/ingest_wal.py": 'SEG = "0001.wal"\n',  # allowed home
    }, ["wal-suffix-confinement"])
    assert len(fs) == 1 and fs[0].path.endswith("sidecar.py")
    assert "'0001.wal'" in fs[0].message


def test_seeded_adhoc_counter(tmp_path):
    fs = findings_for(tmp_path, {
        "data/api/thing.py": "EVENT_COUNTS = {}\nOTHER = []\n",
    }, ["no-adhoc-counters"])
    assert [(f.line, "EVENT_COUNTS" in f.message) for f in fs] == [(1, True)]


def test_seeded_models_dao_confinement(tmp_path):
    fs = findings_for(tmp_path, {
        "workflow/sneaky.py": """
            def load(storage):
                return storage.get_model_data_models().get("id")
            """,
        "workflow/model_artifact.py": """
            def read_model(storage):
                return storage.get_model_data_models().get("id")
            """,
    }, ["models-dao-confinement"])
    assert len(fs) == 1 and fs[0].path.endswith("sneaky.py")


def test_seeded_tenant_confinement(tmp_path):
    fs = findings_for(tmp_path, {
        "workflow/sneaky.py": """
            def peek(server):
                # reaching into the mux's LRU skips the eviction
                # refcount and the per-tenant pin isolation
                return server._tenants._resident_lru.popitem()
            """,
        "workflow/multitenant.py": """
            import collections
            class TenantMux:
                def __init__(self):
                    self._resident_lru = collections.OrderedDict()
                def _evict_victim(self):
                    return None
            """,
    }, ["tenant-confinement"])
    assert len(fs) == 1 and fs[0].path.endswith("sneaky.py")
    assert "_resident_lru outside workflow/multitenant.py" in fs[0].message


def test_seeded_tenant_chokepoint_rename_is_caught(tmp_path):
    """Renaming the LRU attr in the chokepoint module must surface as a
    finding, not silently disarm the guard."""
    fs = findings_for(tmp_path, {
        "workflow/multitenant.py": """
            class TenantMux:
                def __init__(self):
                    self._lru = {}
            """,
    }, ["tenant-confinement"])
    assert len(fs) == 1
    assert "chokepoint" in fs[0].message and "renamed?" in fs[0].message


def test_seeded_query_dispatch_gate(tmp_path):
    fs = findings_for(tmp_path, {"workflow/create_server.py": """
        import asyncio
        class EngineServer:
            async def handle_query(self, request):
                return await asyncio.to_thread(self.deployment.query, {})
        """}, ["query-dispatch-gate"])
    msgs = sorted(f.message for f in fs)
    assert len(fs) == 2
    assert "no longer routes through _dispatch_query" in msgs[0]
    assert "ships query compute to to_thread() directly" in msgs[1]


def test_seeded_lock_discipline(tmp_path):
    fs = findings_for(tmp_path, {"workflow/create_server.py": """
        import threading
        class EngineServer:
            def __init__(self):
                self._lock = threading.Lock()
                self._pinned = {}          # construction: exempt
                self._adm_lock = threading.Lock()
                self._adm_pending = 0
            def good(self):
                with self._lock:
                    return dict(self._pinned)
            def bad(self):
                self._pinned["x"] = "y"    # line 13: unguarded
            def wrong_lock(self):
                with self._adm_lock:
                    self._pinned.pop("x")  # line 16: wrong lock held
        """}, ["lock-discipline"])
    lines = [(f.line, f.message) for f in fs
             if "accessed outside" in f.message]
    assert [ln for ln, _ in lines] == [13, 16]
    assert "self._pinned accessed outside `with self._lock:` in bad()" \
        in lines[0][1]
    # the registry names attrs this seeded tree doesn't have at all —
    # stale entries surface rather than silently guarding nothing
    assert any("stale registry entry" in f.message for f in fs)


def test_seeded_lock_discipline_sees_lambda_bodies(tmp_path):
    """A lambda can't take the lock itself, so a guarded access inside
    one is a finding even when the definition site holds the lock (it
    runs LATER — collector callbacks are the canonical race)."""
    fs = findings_for(tmp_path, {"workflow/create_server.py": """
        import threading
        class EngineServer:
            def __init__(self):
                self._adm_lock = threading.Lock()
                self._adm_pending = 0
                self._lock = threading.Lock()
                self._pinned = {}
                self._pins_provisional = set()
                self._previous = None
                self._rollbacks = {}
                self._swap_count = 0
                self._validate_failures = 0
                self._refresh_swaps = 0
                self._adm_peak = 0
                self._shed_count = 0
                self._deadline_count = 0
                self._orphaned = 0
                self._draining = False
                self._drain_stragglers = 0
            def collectors(self):
                with self._adm_lock:
                    return [lambda: self._adm_pending + 1]  # line 23
        """}, ["lock-discipline"])
    unguarded = [f for f in fs if "accessed outside" in f.message]
    assert [(f.line,) for f in unguarded] == [(23,)]
    assert not any("stale registry entry" in f.message for f in fs)


def test_seeded_lock_discipline_module_scope(tmp_path):
    fs = findings_for(tmp_path, {"parallel/supervisor.py": """
        import threading
        _hb_lock = threading.Lock()
        _hb_last = 0.0
        _hb_interval = None
        def beat():
            global _hb_last
            with _hb_lock:
                _hb_last = 1.0    # guarded: fine
        def peek():
            return _hb_last       # line 11: unguarded module global
        """}, ["lock-discipline"])
    unguarded = [f for f in fs if "accessed outside" in f.message]
    assert [(f.line,) for f in unguarded] == [(11,)]
    assert "_hb_last accessed outside `with _hb_lock:` in peek()" \
        in unguarded[0].message


def test_seeded_blocking_on_loop(tmp_path):
    fs = findings_for(tmp_path, {"data/api/event_server.py": """
        import os
        import time
        class EventServer:
            async def handle(self, request):
                time.sleep(0.1)            # line 6
                names = os.listdir("/x")   # line 7
                with open("f") as fh:      # line 8
                    return fh.read()
            async def fine(self):
                def blocking_is_shipped_off_loop():
                    time.sleep(1)          # nested sync def: exempt
                return blocking_is_shipped_off_loop
            def sync_ok(self):
                time.sleep(0.1)            # not async: out of scope
        """}, ["no-blocking-on-loop"])
    assert sorted(f.line for f in fs) == [6, 7, 8]
    assert all("inside async handle()" in f.message for f in fs)


def test_seeded_knob_envknobs_and_suppression(tmp_path):
    files = {"data/api/knobby.py": """
        import os
        A = os.environ.get("PIO_SEEDED_KNOB")
        B = os.getenv("PIO_SEEDED_KNOB", "x")
        C = os.environ["PIO_SEEDED_KNOB"]
        D = os.environ.get("NOT_A_KNOB")
        """}
    fs = findings_for(tmp_path, files, ["knob-envknobs"])
    assert sorted(f.line for f in fs) == [3, 4, 5]
    # per-line suppression with a reason swallows exactly that line
    files["data/api/knobby.py"] = files["data/api/knobby.py"].replace(
        'A = os.environ.get("PIO_SEEDED_KNOB")',
        'A = os.environ.get("PIO_SEEDED_KNOB")'
        "  # pio-lint: disable=knob-envknobs -- seeded exception")
    project = make_project(tmp_path / "sup", files)
    result = run_lint(project, ALL_RULES, only=["knob-envknobs"])
    assert sorted(f.line for f in result["findings"]) == [4, 5]
    assert result["suppressed"] == 1


def test_seeded_knob_docs_sync_both_directions(tmp_path):
    docs = {"operations.md": """
        | Env | Default | Meaning |
        |---|---|---|
        | `PIO_SEEDED_DOCUMENTED` | 1 | real |
        | `PIO_SEEDED_DEAD_ROW` | 1 | gone from code |
        """}
    fs = findings_for(tmp_path, {"data/api/knobby.py": """
        from ...common.envknobs import env_int
        A = env_int("PIO_SEEDED_DOCUMENTED", 1)
        B = env_int("PIO_SEEDED_UNDOCUMENTED", 2)
        """}, ["knob-docs-sync"], docs=docs)
    assert len(fs) == 2
    undocumented = next(f for f in fs if "PIO_SEEDED_UNDOCUMENTED"
                        in f.message)
    assert undocumented.line == 4 and "no row" in undocumented.message
    dead = next(f for f in fs if "PIO_SEEDED_DEAD_ROW" in f.message)
    assert dead.path == "docs/operations.md" and dead.line == 5
    assert "delete the dead row" in dead.message


def test_seeded_fault_point_registry(tmp_path):
    docs = {"operations.md": "Points: `seeded.documented` exists.\n"}
    fs = findings_for(tmp_path, {"data/api/chaotic.py": """
        from ...common.faultinject import fault_point
        def work(name):
            fault_point("seeded.documented")
            fault_point("seeded.undocumented")
            fault_point("BadConvention")
            fault_point(name)     # variable: out of static reach
        """}, ["fault-point-registry"], docs=docs)
    assert sorted((f.line, f.message.split()[2]) for f in fs) == [
        (5, "'seeded.undocumented'"), (6, "'BadConvention'")]
    assert any("naming convention" in f.message for f in fs)


def test_seeded_metric_name_registry(tmp_path):
    docs = {"operations.md": "| `pio_seeded_documented_total` | counter |\n"}
    fs = findings_for(tmp_path, {"common/metricky.py": """
        import contextvars
        from . import telemetry
        A = telemetry.registry().counter(
            "pio_seeded_documented_total", "fine")
        B = telemetry.registry().counter(
            "pio_seeded_bad_counter", "no _total suffix")
        # ContextVar debug names are identifiers, not families: exempt
        V = contextvars.ContextVar("pio_seeded_ctxvar", default=None)
        """}, ["metric-name-registry"], docs=docs)
    msgs = sorted(f.message for f in fs)
    assert len(fs) == 2  # convention AND undocumented, same family
    assert "must end in _total" in msgs[0]
    assert "'pio_seeded_bad_counter' is not documented" in msgs[1]
    assert not any("pio_seeded_ctxvar" in m for m in msgs)


def test_seeded_tier_literal_confinement(tmp_path):
    """The retention-tier extension of wal-suffix-confinement: the
    retired/ dir name and the cold-archive namespace are exact-match
    string constants only event_log.py may spell."""
    fs = findings_for(tmp_path, {
        "data/storage/side.py":
            'TIER = "retired"\nNS = "pio_eventlog_archive"\n',
        # the allowed home: the tier lifecycle's own module
        "data/api/event_log.py":
            'RETIRED_DIR = "retired"\n'
            'ARCHIVE_NAMESPACE = "pio_eventlog_archive"\n',
        # prose mentioning the word is NOT an artifact reference
        "data/storage/prose.py":
            '"""Rows from a generation retired last week."""\nX = 1\n',
    }, ["wal-suffix-confinement"])
    assert sorted((f.path.endswith("side.py"), f.line) for f in fs) == \
        [(True, 1), (True, 2)]
    assert all("retention-tier artifact name" in f.message for f in fs)
    assert any("'retired'" in f.message for f in fs)
    assert any("'pio_eventlog_archive'" in f.message for f in fs)


def test_seeded_window_metric_family_registry(tmp_path):
    """The windowed-read metric families go through the same doc-driven
    registry: an undocumented pio_train_window_* family is a finding,
    a documented one is not."""
    docs = {"operations.md":
            "| `pio_train_window_generations_skipped_total` | counter "
            "|\n"}
    fs = findings_for(tmp_path, {"common/winmetrics.py": """
        from . import telemetry
        A = telemetry.registry().counter(
            "pio_train_window_generations_skipped_total", "documented")
        B = telemetry.registry().counter(
            "pio_train_window_rows_filtered_total", "not in the docs")
        """}, ["metric-name-registry"], docs=docs)
    assert len(fs) == 1
    assert "'pio_train_window_rows_filtered_total' is not documented" \
        in fs[0].message


def test_seeded_parse_error_is_a_finding(tmp_path):
    project = make_project(tmp_path, {"data/api/broken.py": "def f(:\n"})
    result = run_lint(project, ALL_RULES)
    pe = [f for f in result["findings"] if f.rule == "parse-error"]
    assert len(pe) == 1 and pe[0].path.endswith("broken.py")


def test_unused_suppression_is_a_finding(tmp_path):
    project = make_project(tmp_path, {"data/api/clean.py": """
        X = 1  # pio-lint: disable=knob-envknobs -- nothing here anymore
        Y = 2  # pio-lint: disable=not-a-rule -- typo'd name
        """})
    result = run_lint(project, ALL_RULES)
    unused = sorted(f.message for f in result["findings"]
                    if f.rule == "unused-suppression")
    assert len(unused) == 2
    assert "'knob-envknobs' is unused (nothing to suppress here)" \
        in unused[0]
    assert "'not-a-rule' is unused (unknown rule)" in unused[1]
    # restricted runs skip the unused check (a single rule can't know)
    restricted = run_lint(make_project(tmp_path / "r", {
        "data/api/clean.py": "X = 1  # pio-lint: disable=knob-envknobs\n"}),
        ALL_RULES, only=["knob-envknobs"])
    assert restricted["findings"] == []


def test_unknown_rule_name_raises():
    with pytest.raises(ValueError, match="unknown rule"):
        run_lint(Project.from_repo(), ALL_RULES, only=["no-such-rule"])


# ---------------------------------------------------------------------------
# guard-migration guard (satellite 1): the historical violation class
# re-introduced into a COPY of the real module re-surfaces the finding
# ---------------------------------------------------------------------------

def test_migration_kept_coverage_on_real_event_server(tmp_path):
    """Inject `self.storage.get_l_events().insert(...)` into the REAL
    handle_create body and assert the consolidated rule still flags it
    — proof the engine rewrite kept the legacy guard's teeth on the
    actual source, not just on synthetic trees."""
    src = (PKG / "data" / "api" / "event_server.py").read_text()
    tree = ast.parse(src)
    cls = next(n for n in ast.walk(tree)
               if isinstance(n, ast.ClassDef) and n.name == "EventServer")
    fn = next(n for n in ast.walk(cls)
              if isinstance(n, ast.AsyncFunctionDef)
              and n.name == "handle_create")
    insert_at = fn.body[0].lineno - 1    # before the first body stmt
    indent = " " * fn.body[0].col_offset
    lines = src.splitlines()
    lines.insert(insert_at,
                 f"{indent}self.storage.get_l_events().insert(None, 0)")
    violated = "\n".join(lines) + "\n"
    fs = findings_for(tmp_path, {"data/api/event_server.py": violated},
                      ["ingest-hot-path"])
    assert [(f.line, "`.insert(`" in f.message) for f in fs] == [
        (insert_at + 1, True)]


def test_migration_kept_coverage_on_real_create_server(tmp_path):
    """Same proof for the PR 9 race class: an unguarded `self._pinned`
    mutation added to the real create_server.py fails lock-discipline."""
    src = (PKG / "workflow" / "create_server.py").read_text()
    marker = "    def overload_snapshot(self) -> dict:"
    assert marker in src
    violated = src.replace(marker, (
        "    def sneak_a_pin(self):\n"
        "        self._pinned['x'] = 'race'\n\n" + marker), 1)
    fs = findings_for(tmp_path,
                      {"workflow/create_server.py": violated},
                      ["lock-discipline"])
    flagged = [f for f in fs if "sneak_a_pin" in f.message]
    assert len(flagged) == 1
    assert "self._pinned accessed outside `with self._lock:`" \
        in flagged[0].message


# ---------------------------------------------------------------------------
# regression tests for the defects the new rules surfaced (satellite 2)
# ---------------------------------------------------------------------------

def test_lease_verify_after_release_fences_cleanly(tmp_path):
    """Pre-fix: a commit-thread verify() racing shutdown's release()
    could os.pread(None) → bare TypeError escaping the fence contract.
    Now a released lease verifies as FENCED (refuse the write), always."""
    from incubator_predictionio_tpu.data.api import event_log

    lease = event_log.claim_partition(str(tmp_path), 0)
    lease.verify()              # held: fine
    lease.release()
    with pytest.raises(event_log.PartitionFencedError):
        lease.verify()
    lease.release()             # idempotent


def test_ingest_shed_map_is_thread_safe():
    """Pre-fix: commit threads mutated IngestBuffer._shed while the
    loop iterated it (the PR 8 list() band-aid). Now every access holds
    _shed_lock (lint-enforced); hammer the three paths from threads and
    assert accounting converges with no RuntimeError."""
    from incubator_predictionio_tpu.data.api.ingest_buffer import (
        IngestBuffer, IngestConfig)

    buf = IngestBuffer(None, None, None, config=IngestConfig())
    stop = threading.Event()
    errors = []

    def noter(i):
        k = (i % 4, None)
        try:
            while not stop.is_set():
                buf._note_append_error(k, "faulted")
                buf._note_append_ok(k)
        except Exception as e:  # noqa: BLE001 - the assertion
            errors.append(e)

    def snapshotter():
        try:
            while not stop.is_set():
                buf.snapshot()
        except Exception as e:  # noqa: BLE001 - the assertion
            errors.append(e)

    threads = [threading.Thread(target=noter, args=(i,)) for i in range(4)]
    threads += [threading.Thread(target=snapshotter) for _ in range(2)]
    for t in threads:
        t.start()
    import time
    time.sleep(0.4)
    stop.set()
    for t in threads:
        t.join()
    assert not errors, errors
    snap = buf.snapshot()
    assert snap.get("shedding", 0) <= 4


def test_admission_counters_exact_under_contention():
    """The _adm_lock discipline the rule now enforces: slots taken and
    released across 8 threads leave pending at exactly zero and peak at
    most the admitted cap (a lost-update race would drift pending)."""
    from incubator_predictionio_tpu.workflow.create_server import (
        AdmissionShed, EngineServer)

    s = EngineServer.__new__(EngineServer)
    s._init_overload_state(query_conc=4, query_max_pending=8)
    shed = []

    def churn():
        for _ in range(2000):
            try:
                s._admit()
            except AdmissionShed:
                shed.append(1)
            else:
                s._release_slot()

    threads = [threading.Thread(target=churn) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = s.overload_snapshot()
    assert snap["pending"] == 0
    assert 0 < snap["peakPending"] <= 12
    s._query_executor.shutdown(wait=False)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_rc1_and_json_on_seeded_violation(tmp_path, capsys):
    make_project(tmp_path, {"data/api/knobby.py": """
        import os
        A = os.environ.get("PIO_SEEDED_KNOB")
        """})
    rc = lint_cli(["--root", str(tmp_path), "--rule", "knob-envknobs",
                   "--json"])
    out = capsys.readouterr().out
    assert rc == 1
    doc = json.loads(out)
    assert doc["clean"] is False
    assert doc["findings"][0]["rule"] == "knob-envknobs"
    assert doc["findings"][0]["line"] == 3
    assert doc["findings"][0]["path"].endswith("knobby.py")


def test_cli_clean_rc0_and_filters(tmp_path, capsys):
    make_project(tmp_path, {"data/api/fine.py": "X = 1\n"})
    assert lint_cli(["--root", str(tmp_path)]) == 0
    assert lint_cli(["--list-rules"]) == 0
    out = capsys.readouterr().out
    assert "lock-discipline" in out and "knob-envknobs" in out
    assert lint_cli(["--rule", "definitely-not-a-rule"]) == 2
    # an empty selection must not report "clean" with rc 0
    assert lint_cli(["--rule", ","]) == 2


def test_console_lint_verb_never_imports_jax():
    """`pio lint` must stay a pure parse pass: the console dispatches
    it before any jax-touching setup, so
    a full run fits tier-1 in seconds. Subprocess-proved — including
    the ISSUE 11 whole-program flow rules (call graph + tests/ scan)
    and the --profile path, which must stay equally import-light."""
    r = subprocess.run(
        [sys.executable, "-c",
         "import sys\n"
         "from incubator_predictionio_tpu.tools.console import main\n"
         # ONE full run covers all 17 rules — the ISSUE 11 flow family
         # included (call-graph build + tests/ fault-spec scan), and
         # --profile proves the timing path is equally import-light
         "rc = main(['lint', '--profile'])\n"
         "assert rc == 0, rc\n"
         "assert 'jax' not in sys.modules, 'pio lint imported jax'\n"
         "assert 'aiohttp' not in sys.modules, 'pio lint imported aiohttp'\n"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    # the profile table names the flow rules: they RAN in that process
    assert "transitive-blocking-on-loop" in r.stderr
    assert "fault-point-coverage" in r.stderr


# ---------------------------------------------------------------------------
# ISSUE 14: soak registry rules (SLO metrics documented, fault points armed)
# ---------------------------------------------------------------------------

def test_soak_slo_registry_seeded_violations(tmp_path):
    files = {
        "workflow/soak.py": '''
            SLO_METRICS = (
                "pio_documented_total",
                "pio_ghost_family_total",
                "BadName_total",
            )
            FAULT_POINTS = {}
        ''',
    }
    docs = {"operations.md": "| `pio_documented_total` | counts |\n"}
    fs = findings_for(tmp_path, files, ["soak-slo-registry"], docs)
    msgs = [f.message for f in fs]
    assert len(fs) == 2, msgs
    assert any("pio_ghost_family_total" in m
               and "not a documented metric family" in m for m in msgs)
    assert any("BadName_total" in m and "naming convention" in m
               for m in msgs)
    # a renamed/removed registry literal is itself a finding, never a
    # silent pass
    fs = findings_for(
        tmp_path / "renamed", {"workflow/soak.py": "OTHER = 1\n"},
        ["soak-slo-registry"], docs)
    assert len(fs) == 1 and "SLO_METRICS" in fs[0].message
    # no soak module at all (seeded trees for other rules): clean
    assert findings_for(tmp_path / "nosoak",
                        {"workflow/other.py": "X = 1\n"},
                        ["soak-slo-registry"], docs) == []


def test_soak_fault_registry_seeded_violations(tmp_path):
    files = {
        "workflow/soak.py": '''
            SLO_METRICS = ()
            FAULT_POINTS = {
                "worker_kill": "ingest.commit",
                "ghost_fault": "nobody.arms",
            }
        ''',
        "data/api/thing.py": '''
            from ...common import faultinject

            def commit():
                faultinject.fault_point("ingest.commit")
        ''',
    }
    fs = findings_for(tmp_path, files, ["soak-fault-registry"])
    assert len(fs) == 1, [f.message for f in fs]
    assert "ghost_fault" in fs[0].message
    assert "nobody.arms" in fs[0].message
    # the registry literal disappearing is a finding
    fs = findings_for(
        tmp_path / "renamed", {"workflow/soak.py": "SLO_METRICS = ()\n"},
        ["soak-fault-registry"])
    assert len(fs) == 1 and "FAULT_POINTS" in fs[0].message


# ---------------------------------------------------------------------------
# ISSUE 16: the quality vertical is inside the registries' reach
# ---------------------------------------------------------------------------

def test_seeded_quality_metric_family_coverage(tmp_path):
    """metric-name-registry covers `pio_engine_quality_*`: the
    family's registrations red without their docs rows and go clean
    with them — so docs/operations.md's quality table is enforced, not
    decorative."""
    src = """
        from . import telemetry
        B = telemetry.registry().counter(
            "pio_engine_quality_breaches_total", "breach verdicts")
        M = telemetry.registry().gauge(
            "pio_engine_quality_metric", "live quality", ("metric",))
        """
    fs = findings_for(tmp_path, {"common/qualmetrics.py": src},
                      ["metric-name-registry"],
                      docs={"operations.md": "no rows here\n"})
    assert len(fs) == 2, [f.message for f in fs]
    assert all("is not documented" in f.message for f in fs)
    assert findings_for(
        tmp_path / "docd", {"common/qualmetrics.py": src},
        ["metric-name-registry"],
        docs={"operations.md":
              "| `pio_engine_quality_breaches_total` | counter |\n"
              "| `pio_engine_quality_metric` | gauge |\n"}) == []


def test_seeded_quality_slo_row_coverage(tmp_path):
    """soak-slo-registry covers the quality-regression SLO row's
    evidence families: dropping one of its docs rows is a finding, so
    the scorecard cannot assert evidence nothing documents."""
    files = {"workflow/soak.py": '''
        SLO_METRICS = (
            "pio_engine_quality_samples_total",
            "pio_engine_quality_breaches_total",
        )
        FAULT_POINTS = {}
    '''}
    assert findings_for(
        tmp_path, files, ["soak-slo-registry"],
        {"operations.md":
         "| `pio_engine_quality_samples_total` | counter |\n"
         "| `pio_engine_quality_breaches_total` | counter |\n"}) == []
    fs = findings_for(
        tmp_path / "red", files, ["soak-slo-registry"],
        {"operations.md":
         "| `pio_engine_quality_samples_total` | counter |\n"})
    assert len(fs) == 1, [f.message for f in fs]
    assert "pio_engine_quality_breaches_total" in fs[0].message
    assert "not a documented metric family" in fs[0].message


def test_seeded_train_feed_confinement(tmp_path):
    """Training-path modules (workflow/ + ops/) may not read events
    through the merged view or touch shard files directly; the same
    code OUTSIDE the training path (data/api — where the partition
    feed itself lives) is clean."""
    src = '''
        def read(store, app):
            scan = store._merged_scan(app, None, [])
            for b in store.find_batches(app):
                pass
            return scan
    '''
    fs = findings_for(tmp_path / "wf", {"workflow/rogue_read.py": src},
                      ["train-feed-confinement"])
    assert len(fs) == 2
    assert any("_merged_scan" in f.message for f in fs)
    assert any("find_batches" in f.message for f in fs)
    shard_src = '''
        from ..data.storage.jsonl import scan_log_file, shard_paths

        def feed(d, app):
            return [scan_log_file(p) for p in shard_paths(d, app)]
    '''
    fs = findings_for(tmp_path / "ops", {"ops/rogue_feed.py": shard_src},
                      ["train-feed-confinement"])
    assert len(fs) >= 2
    assert {m for f in fs for m in ("shard_paths", "scan_log_file")
            if m in f.message} == {"shard_paths", "scan_log_file"}
    # the reader API itself (data/api/) is outside the rule's scope
    assert findings_for(
        tmp_path / "api", {"data/api/partition_feed.py": shard_src},
        ["train-feed-confinement"]) == []


def test_spawn_confinement_still_fires_outside_the_soak_driver(tmp_path):
    """The soak driver's spawn exemption must not widen the rule: any
    OTHER workflow/ module spawning a process is still a finding."""
    src = '''
        import subprocess

        def launch():
            subprocess.Popen(["x"])
    '''
    fs = findings_for(tmp_path / "rogue", {"workflow/rogue.py": src},
                      ["spawn-confinement"])
    assert len(fs) == 1 and "rogue" in fs[0].path
    assert findings_for(tmp_path / "driver", {"workflow/soak.py": src},
                        ["spawn-confinement"]) == []


# ---------------------------------------------------------------------------
# ISSUE 17: million-item serving (sharded top-k facade + query cache)
# ---------------------------------------------------------------------------

def test_seeded_sharded_topk_confinement(tmp_path):
    """Template code under models/ may not reach ops.sharded_topk
    directly — the _sharded_serving facade is the single place the
    mesh/flat layout choice (and its bit-identity contract)
    lives. The facade itself is exempt; ops/ code is out of scope."""
    rogue = '''
        from ..ops.sharded_topk import sharded_top_k_items
        from ..ops import sharded_topk

        def score(vec, factors, mesh, k):
            cat = sharded_topk.put_sharded_catalog(factors, mesh)
            return sharded_top_k_items(vec, cat, k)
    '''
    fs = findings_for(tmp_path, {"models/rogue_template.py": rogue},
                      ["sharded-topk-confinement"])
    assert len(fs) == 3, [f.message for f in fs]
    assert all("_sharded_serving facade" in f.message for f in fs)
    assert any("sharded_topk.put_sharded_catalog" in f.message
               for f in fs)
    # the facade is the ONE legal home
    assert findings_for(
        tmp_path / "facade", {"models/_sharded_serving.py": rogue},
        ["sharded-topk-confinement"]) == []
    # ops/ implements the kernels; the rule scopes to models/ only
    assert findings_for(
        tmp_path / "ops", {"ops/other_kernels.py": rogue},
        ["sharded-topk-confinement"]) == []


def test_seeded_query_cache_metric_family_coverage(tmp_path):
    """metric-name-registry covers `pio_query_cache_*`: the families
    red without their docs rows and go clean with them, and a
    non-`_total` cache counter is a convention finding."""
    src = """
        from . import telemetry
        H = telemetry.registry().counter(
            "pio_query_cache_hits_total", "cache hits")
        I = telemetry.registry().counter(
            "pio_query_cache_invalidations_total", "by trigger",
            ("reason",))
        B = telemetry.registry().counter(
            "pio_query_cache_evictions", "no _total suffix")
        """
    docs = {"operations.md":
            "| `pio_query_cache_hits_total` | counter |\n"
            "| `pio_query_cache_invalidations_total` | counter |\n"}
    fs = findings_for(tmp_path, {"common/cachemetrics.py": src},
                      ["metric-name-registry"], docs=docs)
    assert len(fs) == 2, [f.message for f in fs]  # convention + undocumented
    assert any("must end in _total" in f.message for f in fs)
    assert any("'pio_query_cache_evictions' is not documented"
               in f.message for f in fs)
    fs = findings_for(
        tmp_path / "red", {"common/cachemetrics.py": src.replace(
            'B = telemetry.registry().counter(\n'
            '            "pio_query_cache_evictions", "no _total suffix")',
            "")},
        ["metric-name-registry"], docs={"operations.md": "no rows\n"})
    assert len(fs) == 2, [f.message for f in fs]
    assert all("is not documented" in f.message for f in fs)
