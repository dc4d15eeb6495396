"""tpuracer tests: the cross-file project index (thread entries, lock
inventory, acquisition-order graph, attribute ownership) and the rules
riding it — TPL007 lock-order inversion, TPL008 unlocked shared
writes, TPL009 blocking-under-lock, TPL010 env-registry drift, TPL011
metrics-contract drift — plus the CLI surfaces (--threads, --changed,
hard TPL000 findings for rotten inputs) and the `paddle_tpu._env`
accessor semantics the registry contract rests on."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from paddle_tpu import _env
from paddle_tpu.analysis import LintConfig, lint_source
from paddle_tpu.analysis.context import FileContext
from paddle_tpu.analysis.project import (CALLER_ENTRY, ProjectIndex,
                                         pretty_key)
from paddle_tpu.analysis.runner import analyze_paths, lint_paths

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TPULINT = os.path.join(REPO, "tools", "tpulint.py")

# any path with this suffix lands in the default concurrency_scope /
# env_migrated / lock_scope globs
SCOPED = "paddle_tpu/serving/fixture.py"


def run(src, path=SCOPED, config=None):
    return lint_source(textwrap.dedent(src), path=path,
                       config=config or LintConfig.default())


def rule_ids(src, **kw):
    return sorted({f.rule for f in run(src, **kw) if not f.suppressed})


def build_index(files, config=None):
    """ProjectIndex over {path: source} without the rule layer."""
    config = config or LintConfig.default()
    ctxs = [FileContext(p, textwrap.dedent(s), config)
            for p, s in sorted(files.items())]
    return ProjectIndex.build(ctxs, config)


def write_tree(tmp_path, files):
    """Materialize {relpath: source} under tmp_path; returns the root
    as a string for lint_paths/CLI runs."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return str(tmp_path)


def _cli(*args, cwd=REPO):
    return subprocess.run([sys.executable, TPULINT, *args], cwd=cwd,
                          capture_output=True, text=True, timeout=120)


# ===================================================== TPL007 lock order
INVERTED = """
    import threading

    class Pair:
        def __init__(self):
            self._a = threading.Lock()
            self._b = threading.Lock()

        def forward(self):
            with self._a:
                with self._b:
                    pass

        def backward(self):
            with self._b:
                with self._a:
                    pass
"""


class TestLockOrder:
    def test_fires_on_inverted_nesting(self):
        out = [f for f in run(INVERTED) if f.rule == "TPL007"]
        assert len(out) == 1                 # one finding per cycle
        assert "lock-order inversion" in out[0].message
        assert "Pair._a" in out[0].message and "Pair._b" in out[0].message

    def test_silent_on_consistent_order(self):
        assert "TPL007" not in rule_ids("""
            import threading

            class Pair:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def forward(self):
                    with self._a:
                        with self._b:
                            pass

                def also_forward(self):
                    with self._a:
                        with self._b:
                            pass
        """)

    def test_fires_across_classes_via_calls(self):
        """The inversion hides behind a call edge: Left holds its lock
        and calls into Right, which holds its own and calls back."""
        assert "TPL007" in rule_ids("""
            import threading

            class Right:
                def __init__(self):
                    self._rlock = threading.Lock()
                    self.left = Left()

                def poke(self):
                    with self._rlock:
                        self.left.nudge()

            class Left:
                def __init__(self):
                    self._llock = threading.Lock()
                    self.right = Right()

                def nudge(self):
                    with self._llock:
                        self.right.poke()
        """)

    def test_unit_cycle_witness(self):
        idx = build_index({SCOPED: INVERTED})
        cycles = idx.lock_cycles()
        assert len(cycles) == 1
        ids, witness = cycles[0]
        assert ids == ["Pair._a", "Pair._b"]
        assert witness.path == SCOPED

    def test_unit_transitive_edge_through_call(self):
        idx = build_index({SCOPED: """
            import threading

            class C:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def outer(self):
                    with self._a:
                        self._inner()

                def _inner(self):
                    with self._b:
                        pass
        """})
        edges = {(e.src, e.dst) for e in idx.lock_order_edges()}
        assert ("C._a", "C._b") in edges
        assert not idx.lock_cycles()


# ================================================ TPL008 shared writes
RACY = """
    import threading

    class Worker:
        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0

        def start(self):
            threading.Thread(target=self._pump, name="pt-pump").start()
            threading.Thread(target=self._drain).start()

        def _pump(self):
            self.count = self.count + 1

        def _drain(self):
            self.count = 0
"""


class TestSharedWrites:
    def test_fires_on_two_thread_writers_no_lock(self):
        out = [f for f in run(RACY) if f.rule == "TPL008"]
        assert len(out) == 1
        assert "self.count" in out[0].message
        assert "Worker._pump" in out[0].message
        assert "Worker._drain" in out[0].message

    def test_silent_with_common_lock(self):
        assert "TPL008" not in rule_ids("""
            import threading

            class Worker:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.count = 0

                def start(self):
                    threading.Thread(target=self._pump).start()
                    threading.Thread(target=self._drain).start()

                def _pump(self):
                    with self._lock:
                        self.count = self.count + 1

                def _drain(self):
                    with self._lock:
                        self.count = 0
        """)

    def test_silent_single_writer_delta_mirror(self):
        """One owning thread writes; everyone else only reads — the
        delta-mirror pattern must not fire."""
        assert "TPL008" not in rule_ids("""
            import threading

            class Worker:
                def __init__(self):
                    self.count = 0

                def start(self):
                    threading.Thread(target=self._pump).start()

                def _pump(self):
                    self.count = self.count + 1

                def peek(self):
                    return self.count
        """)

    def test_locked_suffix_counts_as_holding_class_locks(self):
        """`*_locked` methods document "caller holds the lock"; writes
        inside them share the class lock with `with`-guarded writers."""
        assert "TPL008" not in rule_ids("""
            import threading

            class Worker:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.count = 0

                def start(self):
                    threading.Thread(target=self._pump).start()
                    threading.Thread(target=self._drain).start()

                def _pump(self):
                    with self._lock:
                        self._bump_locked()

                def _bump_locked(self):
                    self.count = self.count + 1

                def _drain(self):
                    with self._lock:
                        self.count = 0
        """)

    def test_unit_entry_points_and_ownership(self):
        idx = build_index({SCOPED: RACY})
        entries = dict(idx.entry_points())
        assert "Worker._pump" in entries
        assert "Worker._drain" in entries
        assert CALLER_ENTRY in entries        # public API pseudo-entry
        owners = idx.ownership_map()
        # __init__ writes are construction, not contention
        assert ("Worker", "count") in owners
        writers = owners[("Worker", "count")]
        assert set(writers) == {"Worker._pump", "Worker._drain"}

    def test_unit_thread_report_carries_name_hint(self):
        idx = build_index({SCOPED: RACY})
        rows = idx.thread_report()
        assert ("pt-pump", "Worker._pump", f"{SCOPED}:10") in rows


# ============================================ TPL009 blocking under lock
class TestBlockingUnderLock:
    def test_fires_on_sendall_under_lock(self):
        out = [f for f in run("""
            import socket
            import threading

            class Client:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._sock = socket.create_connection(("h", 1))

                def send(self, data):
                    with self._lock:
                        self._sock.sendall(data)
        """) if f.rule == "TPL009"]
        assert len(out) == 1
        assert "sendall" in out[0].message
        assert "Client._lock" in out[0].message

    def test_silent_when_lock_is_an_io_mutex(self):
        """*_wlock names declare "this lock serializes one socket" —
        spanning its own sends is the point."""
        assert "TPL009" not in rule_ids("""
            import socket
            import threading

            class Client:
                def __init__(self):
                    self._wlock = threading.Lock()
                    self._sock = socket.create_connection(("h", 1))

                def send(self, data):
                    with self._wlock:
                        self._sock.sendall(data)
        """)

    def test_silent_outside_lock(self):
        assert "TPL009" not in rule_ids("""
            import socket
            import threading

            class Client:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._sock = socket.create_connection(("h", 1))

                def send(self, data):
                    with self._lock:
                        payload = bytes(data)
                    self._sock.sendall(payload)
        """)

    def test_fires_on_queue_get_without_timeout(self):
        out = [f for f in run("""
            import queue
            import threading

            class Pump:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._q = queue.Queue()

                def drain(self):
                    with self._lock:
                        item = self._q.get()
                    return item
        """) if f.rule == "TPL009"]
        assert len(out) == 1
        assert "queue get, no timeout" in out[0].message

    def test_silent_on_queue_get_with_timeout(self):
        assert "TPL009" not in rule_ids("""
            import queue
            import threading

            class Pump:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._q = queue.Queue()

                def drain(self):
                    with self._lock:
                        item = self._q.get(timeout=0.5)
                    return item
        """)

    def test_fires_transitively_across_files(self, tmp_path):
        """node.py holds a lock and calls wire.send_msg, which lives in
        another file and blocks on the socket — the finding lands at
        the call site in node.py and names the hop."""
        root = write_tree(tmp_path, {
            "paddle_tpu/serving/wire.py": """
                def send_msg(sock, payload):
                    sock.sendall(payload)
            """,
            "paddle_tpu/serving/node.py": """
                import threading

                from .wire import send_msg

                class Node:
                    def __init__(self, sock):
                        self._lock = threading.Lock()
                        self.sock = sock

                    def publish(self, payload):
                        with self._lock:
                            send_msg(self.sock, payload)
            """,
        })
        findings, _ = lint_paths([root])
        hits = [f for f in findings if f.rule == "TPL009"]
        assert len(hits) == 1
        assert hits[0].path.endswith("node.py")
        assert "wire.send_msg" in hits[0].message


# ================================================ TPL010 env registry
class TestEnvRegistry:
    def test_fires_on_undeclared_knob(self):
        out = [f for f in run("""
            import os

            def flag():
                return os.environ.get("PT_UNDECLARED_KNOB", "0")
        """) if f.rule == "TPL010"]
        assert len(out) == 1
        assert "PT_UNDECLARED_KNOB" in out[0].message
        assert "not declared" in out[0].message

    def test_fires_on_subscript_and_membership_reads(self):
        out = [f for f in run("""
            import os

            def pair():
                a = os.environ["PT_SUB_KNOB"]
                b = "PT_IN_KNOB" in os.environ
                return a, b
        """) if f.rule == "TPL010"]
        assert {m for f in out for m in ("PT_SUB_KNOB", "PT_IN_KNOB")
                if m in f.message} == {"PT_SUB_KNOB", "PT_IN_KNOB"}

    def test_silent_on_foreign_namespaces(self):
        assert "TPL010" not in rule_ids("""
            import os

            def home():
                return os.environ.get("HOME", "/")
        """)

    def test_declared_knob_raw_read_in_migrated_package(self, tmp_path):
        """A declared knob read via raw os.environ inside a migrated
        package fires; the accessor read is clean; a pattern-family
        member counts as declared."""
        root = write_tree(tmp_path, {
            "paddle_tpu/_env.py": """
                def declare(name, default, doc, *, kind="str",
                            section="general"):
                    return name

                declare("PT_FIXTURE_DEPTH", 8, "test knob", kind="int")
                declare("PT_FIXTURE_*_S", None, "family", kind="float")
            """,
            "paddle_tpu/serving/reader.py": """
                import os

                from .._env import env_float, env_int

                def raw():
                    return os.environ.get("PT_FIXTURE_DEPTH", "8")

                def clean():
                    return (env_int("PT_FIXTURE_DEPTH"),
                            env_float("PT_FIXTURE_WAIT_S", 1.0))
            """,
        })
        findings, _ = lint_paths([root])
        hits = [f for f in findings if f.rule == "TPL010"]
        assert len(hits) == 1
        assert "raw os.environ read of declared knob" in hits[0].message
        assert hits[0].path.endswith("reader.py")


# ============================================ TPL011 metrics contract
def _metrics_config(tmp_path, doc_text):
    doc = tmp_path / "metrics.md"
    doc.write_text(textwrap.dedent(doc_text))
    cfg = LintConfig.default()
    cfg.metrics_docs = [str(doc)]
    return cfg


class TestMetricsContract:
    def test_fires_on_undocumented_booking(self, tmp_path):
        cfg = _metrics_config(tmp_path, """
            | Metric | Meaning |
            |---|---|
            | `pt_documented_total` | counted |
        """)
        out = [f for f in run("""
            def book(r):
                return r.counter("pt_rogue_metric", "no docs row")
        """, path="paddle_tpu/serving/m.py", config=cfg)
            if f.rule == "TPL011"]
        assert len(out) == 1
        assert "pt_rogue_metric" in out[0].message

    def test_total_suffix_tolerance(self, tmp_path):
        """Counters render `<name>_total` in the exposition; docs rows
        using either form match the booking."""
        cfg = _metrics_config(tmp_path, """
            | Metric | Meaning |
            |---|---|
            | `pt_reqs_total` | requests |
        """)
        assert "TPL011" not in rule_ids("""
            def book(r):
                return r.counter("pt_reqs", "requests")
        """, path="paddle_tpu/serving/m.py", config=cfg)

    def test_brace_rows_expand(self, tmp_path):
        cfg = _metrics_config(tmp_path, """
            | Metric | Meaning |
            |---|---|
            | `pt_cache_{hits,misses}_total` | cache outcome |
        """)
        assert "TPL011" not in rule_ids("""
            def book(r):
                a = r.counter("pt_cache_hits", "x")
                b = r.counter("pt_cache_misses", "y")
                return a, b
        """, path="paddle_tpu/serving/m.py", config=cfg)

    def test_ghost_documented_metric_fires_at_registry(self, tmp_path):
        cfg = _metrics_config(tmp_path, """
            | Metric | Meaning |
            |---|---|
            | `pt_ghost_metric` | long gone |
            | `pt_live_metric` | still booked |
        """)
        out = [f for f in run("""
            class MetricsRegistry:
                def counter(self, name, doc):
                    return name

            def book(r):
                return r.counter("pt_live_metric", "alive")
        """, path="paddle_tpu/serving/m.py", config=cfg)
            if f.rule == "TPL011"]
        assert len(out) == 1
        assert "pt_ghost_metric" in out[0].message
        assert "never booked" in out[0].message

    def test_fstring_booking_matches_documented_member(self, tmp_path):
        """f-string bookings (pt_phase_{ph}_seconds) are recorded as
        patterns, so documented concrete members are not ghosts."""
        cfg = _metrics_config(tmp_path, """
            | Metric | Meaning |
            |---|---|
            | `pt_phase_prefill_seconds` | phase split |
        """)
        assert "TPL011" not in rule_ids("""
            class MetricsRegistry:
                def histogram(self, name, doc):
                    return name

            def book(r, ph):
                return r.histogram(f"pt_phase_{ph}_seconds", "split")
        """, path="paddle_tpu/serving/m.py", config=cfg)

    def test_silent_when_no_docs_exist(self, tmp_path):
        cfg = LintConfig.default()
        cfg.metrics_docs = [str(tmp_path / "nope-*.md")]
        assert "TPL011" not in rule_ids("""
            def book(r):
                return r.counter("pt_whatever", "x")
        """, path="paddle_tpu/serving/m.py", config=cfg)


# ================================================= suppression grammar
class TestSuppressions:
    def test_disable_next_line_with_reason(self):
        out = run("""
            import socket
            import threading

            class Client:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._sock = socket.create_connection(("h", 1))

                def send(self, data):
                    with self._lock:
                        # tpulint: disable-next-line=TPL009 -- drill
                        self._sock.sendall(data)
        """)
        hits = [f for f in out if f.rule == "TPL009"]
        assert len(hits) == 1
        assert hits[0].suppressed
        assert hits[0].suppress_reason == "drill"

    def test_trailing_disable_on_witness_line(self):
        src = RACY.replace(
            "self.count = self.count + 1",
            "self.count = self.count + 1  "
            "# tpulint: disable=TPL008 -- fixture")
        hits = [f for f in run(src) if f.rule == "TPL008"]
        assert len(hits) == 1 and hits[0].suppressed


# ======================================================== project index
class TestProjectIndex:
    def test_pretty_key(self):
        assert pretty_key("Worker._pump") == "Worker._pump"
        assert pretty_key("a/b/wire.py::send_msg") == "wire.send_msg"

    def test_env_pattern_declarations(self):
        idx = build_index({"paddle_tpu/_env.py": """
            def declare(name, default, doc, **kw):
                return name

            declare("PT_EXACT", 1, "x")
            declare("PT_FAM_*_S", None, "family")
        """})
        assert idx.env_is_declared("PT_EXACT")
        assert idx.env_is_declared("PT_FAM_DECODE_S")
        assert not idx.env_is_declared("PT_OTHER")
        assert idx.has_env_registry

    def test_reachability_is_transitive(self):
        idx = build_index({SCOPED: """
            class C:
                def a(self):
                    self.b()

                def b(self):
                    self.c()

                def c(self):
                    pass
        """})
        assert idx.reachable(["C.a"]) == {"C.a", "C.b", "C.c"}

    def test_index_is_conservative_on_unresolvable_calls(self):
        """Unknown call targets contribute nothing — no guessed
        findings, no phantom graph nodes."""
        idx = build_index({SCOPED: """
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()

                def go(self, helper):
                    with self._lock:
                        helper.mystery()
        """})
        assert not idx.lock_cycles()
        assert not idx.blocking_under_lock()


# ===================================================== CLI hard findings
class TestCLIHardFindings:
    def test_nonexistent_path_is_a_finding_not_a_skip(self, tmp_path):
        proc = _cli(str(tmp_path / "gone.py"))
        assert proc.returncode == 1
        assert "TPL000" in proc.stdout
        assert "does not exist" in proc.stdout

    def test_unreadable_file_is_a_finding(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_bytes(b"\xff\xfe\xff not utf-8 \xff")
        proc = _cli(str(bad), "--format", "json")
        assert proc.returncode == 1
        doc = json.loads(proc.stdout)
        assert [f["rule"] for f in doc["findings"]] == ["TPL000"]
        assert "cannot read" in doc["findings"][0]["message"]

    def test_syntax_error_is_a_finding_with_location(self, tmp_path):
        bad = tmp_path / "broken.py"
        bad.write_text("def f(:\n    pass\n")
        proc = _cli(str(bad), "--format", "json")
        assert proc.returncode == 1
        doc = json.loads(proc.stdout)
        assert doc["findings"][0]["rule"] == "TPL000"
        assert "syntax error" in doc["findings"][0]["message"]


# ========================================================= CLI --threads
class TestCLIThreads:
    def test_threads_inventory(self, tmp_path):
        root = write_tree(tmp_path, {
            "paddle_tpu/serving/w.py": RACY,
        })
        proc = _cli(root, "--threads")
        assert proc.returncode == 0
        assert "Worker._pump" in proc.stdout
        assert "pt-pump" in proc.stdout
        assert "<caller>" in proc.stdout


# ========================================================= CLI --changed
BAD_SYNC = """
import jax

@jax.jit
def f(x):
    return x.numpy()
"""


class TestCLIChanged:
    def _git(self, repo, *args):
        subprocess.run(
            ["git", "-c", "user.email=t@t", "-c", "user.name=t", *args],
            cwd=repo, check=True, capture_output=True, timeout=30)

    def test_changed_filters_to_touched_files(self, tmp_path):
        (tmp_path / "old.py").write_text(BAD_SYNC)
        self._git(tmp_path, "init", "-q")
        self._git(tmp_path, "add", "old.py")
        self._git(tmp_path, "commit", "-qm", "seed")
        (tmp_path / "new.py").write_text(BAD_SYNC.replace("f(", "g("))

        full = _cli(".", cwd=tmp_path)
        assert full.returncode == 1
        assert "old.py" in full.stdout and "new.py" in full.stdout

        changed = _cli(".", "--changed", "HEAD", cwd=tmp_path)
        assert changed.returncode == 1
        assert "new.py" in changed.stdout
        assert "old.py" not in changed.stdout

    def test_changed_clean_when_touched_files_clean(self, tmp_path):
        (tmp_path / "old.py").write_text(BAD_SYNC)
        self._git(tmp_path, "init", "-q")
        self._git(tmp_path, "add", "old.py")
        self._git(tmp_path, "commit", "-qm", "seed")
        (tmp_path / "new.py").write_text("x = 1\n")
        proc = _cli(".", "--changed", "HEAD", cwd=tmp_path)
        assert proc.returncode == 0

    def test_bad_ref_is_a_usage_error(self, tmp_path):
        (tmp_path / "a.py").write_text("x = 1\n")
        self._git(tmp_path, "init", "-q")
        proc = _cli(".", "--changed", "no-such-ref", cwd=tmp_path)
        assert proc.returncode == 2


# ===================================================== _env accessors
class TestEnvAccessors:
    def test_declared_defaults_flow_through(self):
        assert _env.env_int("PT_PULSE_DEPTH", env={}) == 240
        assert _env.env_int("PT_PULSE_DEPTH", env={"PT_PULSE_DEPTH": "8"}) == 8

    def test_empty_string_falls_back_for_numbers(self):
        assert _env.env_int("PT_PULSE_DEPTH",
                            env={"PT_PULSE_DEPTH": " "}) == 240

    def test_bool_semantics(self):
        assert _env.env_bool("PT_SERVE_TIMING", env={}) is False
        assert _env.env_bool("PT_SERVE_TIMING",
                             env={"PT_SERVE_TIMING": "1"}) is True
        assert _env.env_bool("PT_SERVE_TIMING",
                             env={"PT_SERVE_TIMING": "0"}) is False
        assert _env.env_bool("PT_SERVE_TIMING",
                             env={"PT_SERVE_TIMING": ""}) is False

    def test_undeclared_name_raises(self):
        with pytest.raises(KeyError):
            _env.env_str("PT_NOT_A_KNOB", env={})

    def test_pattern_family_requires_call_site_default(self):
        fam = [k for k in _env.knobs() if k.is_pattern]
        assert fam, "expected at least one pattern family knob"
        member = fam[0].name.replace("*", "X")
        assert _env.is_declared(member)
        with pytest.raises(KeyError):
            _env.env_str(member, env={})
        assert _env.env_str(member, "fallback", env={}) == "fallback"


# ============================================== two-phase runner seams
class TestAnalyzePaths:
    def test_rule_subset_still_builds_full_index(self, tmp_path):
        root = write_tree(tmp_path, {"paddle_tpu/serving/w.py": RACY})
        findings, nfiles, project = analyze_paths([root])
        assert nfiles == 1
        assert any(f.rule == "TPL008" for f in findings)
        assert project.thread_entries
