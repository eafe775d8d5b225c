"""graftlint Pass 3a gates (ISSUE 7): lock-discipline lint unit cases.

The fixture test (test_graftlint.py) pins exact per-rule counts on the
shared fixture; this file pins the rule SEMANTICS — scope heuristics,
the write-once exemption, guard-map inference and annotation, cross-
module cycle unification, the dispatch-lock exemption, and stale-
suppression detection — each on a minimal snippet, so a behavior drift
names the exact heuristic that moved.
"""

import os
import subprocess
import sys

from milnce_tpu.analysis.astlint import lint_paths, lint_source

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _ids(src, **kw):
    return [f.rule.id for f in lint_source(src, **kw) if not f.suppressed]


# ---------------------------------------------------------------------------
# GL010 unguarded-shared-state
# ---------------------------------------------------------------------------

_SHARED_WRITE = """
import threading

class Stats:
    def __init__(self):
        self._lock = threading.Lock()
        self.calls = 0

    def record(self):
        with self._lock:
            pass
        self.calls += 1

    def snapshot(self):
        return self.calls
"""


def test_unguarded_shared_write_flagged():
    assert _ids(_SHARED_WRITE) == ["GL010"]


def test_single_root_attr_is_not_shared():
    """An attribute reachable from ONE thread root only (the
    ShardedLoader.decode_timeouts pattern: consumer-thread-private
    bookkeeping) is not shared state — no finding."""
    src = _SHARED_WRITE.replace("    def snapshot(self):\n"
                                "        return self.calls\n", "")
    assert _ids(src) == []


def test_write_once_read_exempt_and_guarded_read_flagged():
    src = """
import threading

class Box:
    def __init__(self):
        self._lock = threading.Lock()
        self.mode = "ladder"
        self.count = 0

    def bump(self):
        with self._lock:
            self.count += 1

    def peek(self):
        return (self.mode, self.count)
"""
    findings = [f for f in lint_source(src) if not f.suppressed]
    # exactly one: the lock-free read of guarded `count`; the write-once
    # `mode` read is exempt
    assert [f.rule.id for f in findings] == ["GL010"]
    assert "count" in findings[0].message
    assert "mode" not in findings[0].message


def test_guarded_by_annotation_audits_lock_free_reads():
    """An annotated write-once attribute reads lock-free without a
    finding; the same annotation on a mutated attribute still flags
    unguarded writes."""
    ok = """
import threading

class Cfg:
    def __init__(self):
        self._lock = threading.Lock()
        self.depth = 2  # guarded-by: _lock

    def use(self):
        with self._lock:
            pass
        return self.depth
"""
    assert _ids(ok) == []
    # once mutated it is no longer write-once: the unguarded write AND
    # the now-racy lock-free read both fire
    bad = ok.replace("        return self.depth",
                     "        self.depth = 3\n        return self.depth")
    assert _ids(bad) == ["GL010", "GL010"]


def test_unknown_guarded_by_lock_is_gl000():
    src = """
import threading

class Cfg:
    def __init__(self):
        self._lock = threading.Lock()
        self.depth = 2  # guarded-by: _lok

    def use(self):
        with self._lock:
            pass
"""
    findings = lint_source(src)
    assert [f.rule.id for f in findings] == ["GL000"]
    assert "_lok" in findings[0].message


def test_method_level_guarded_by_means_caller_holds_the_lock():
    """A private helper annotated `# guarded-by:` on its def line is
    analyzed as if the lock were held throughout (the helper-relies-on-
    caller pattern)."""
    src = """
import threading

class Stats:
    def __init__(self):
        self._lock = threading.Lock()
        self.calls = 0

    def record(self):
        with self._lock:
            self._bump()

    def _bump(self):  # guarded-by: _lock
        self.calls += 1

    def snapshot(self):
        with self._lock:
            return self.calls
"""
    assert _ids(src) == []


def test_lockless_single_threaded_class_out_of_scope():
    """A class with no locks, no threads, no HTTP handlers mutates its
    attributes freely — Pass 3 must not police ordinary objects."""
    src = """
class Accum:
    def __init__(self):
        self.total = 0

    def add(self, x):
        self.total += x

    def value(self):
        return self.total
"""
    assert _ids(src) == []


def test_thread_target_private_method_is_a_root():
    """Thread(target=self._run) makes the private worker a thread root:
    state it shares with a public method needs a guard."""
    src = """
import threading

class Worker:
    def __init__(self):
        self.count = 0
        self._t = threading.Thread(target=self._run)

    def _run(self):
        self.count += 1

    def stats(self):
        return self.count
"""
    assert _ids(src) == ["GL010"]


# ---------------------------------------------------------------------------
# GL011 lock-order-cycle
# ---------------------------------------------------------------------------

_CYCLE = """
import threading

A = threading.Lock()
B = threading.Lock()

def one():
    with A:
        with B:
            pass

def two():
    with B:
        with A:
            pass
"""


def test_two_lock_cycle_detected():
    assert _ids(_CYCLE) == ["GL011"]


def test_consistent_order_is_clean():
    consistent = _CYCLE.replace("    with B:\n        with A:",
                                "    with A:\n        with B:")
    assert _ids(consistent) == []


def test_cycle_through_same_module_call_detected():
    """with A: helper() where helper takes B, plus the inverse order
    elsewhere — the interprocedural edge closes the cycle."""
    src = """
import threading

A = threading.Lock()
B = threading.Lock()

def helper():
    with B:
        pass

def one():
    with A:
        helper()

def two():
    with B:
        with A:
            pass
"""
    assert _ids(src) == ["GL011"]


def test_cross_module_cycle_via_imported_lock(tmp_path):
    """AB in one module, BA in another, joined by an imported
    module-level lock (the DEVICE_DISPATCH_LOCK shape) — only the
    merged graph contains the cycle."""
    a = tmp_path / "mod_a.py"
    b = tmp_path / "mod_b.py"
    a.write_text(
        "import threading\n"
        "ALPHA_LOCK = threading.Lock()\n"
        "BETA_LOCK = threading.Lock()\n"
        "def one():\n"
        "    with ALPHA_LOCK:\n"
        "        with BETA_LOCK:\n"
        "            pass\n")
    b.write_text(
        "from mod_a import ALPHA_LOCK, BETA_LOCK\n"
        "def two():\n"
        "    with BETA_LOCK:\n"
        "        with ALPHA_LOCK:\n"
        "            pass\n")
    # each module alone is clean...
    assert [f.rule.id for f in lint_paths([str(a)])] == []
    # ...the union has the cycle
    ids = [f.rule.id for f in lint_paths([str(a), str(b)])]
    assert ids == ["GL011"], ids


# ---------------------------------------------------------------------------
# GL012 blocking-under-lock
# ---------------------------------------------------------------------------

def test_future_result_under_lock_flagged():
    src = """
import threading

L = threading.Lock()

def wait(fut):
    with L:
        return fut.result()
"""
    assert _ids(src) == ["GL012"]
    # ...and the same call outside the critical section is fine
    clean = src.replace("    with L:\n        return fut.result()",
                        "    with L:\n        pass\n    return fut.result()")
    assert _ids(clean) == []


def test_str_join_under_lock_not_confused_with_thread_join():
    src = """
import threading

L = threading.Lock()

def fmt(parts, worker):
    with L:
        label = ",".join(parts)
        worker.join()
    return label
"""
    findings = [f for f in lint_source(src) if not f.suppressed]
    assert [f.rule.id for f in findings] == ["GL012"]
    assert findings[0].message.startswith(".join()")


def test_device_dispatch_exempt_only_under_dispatch_named_lock():
    dispatch = """
import threading
import jax

DEVICE_DISPATCH_LOCK = threading.Lock()

def run(fn, x, sh):
    with DEVICE_DISPATCH_LOCK:
        return jax.device_get(fn(jax.device_put(x, sh)))
"""
    assert _ids(dispatch) == []
    other = dispatch.replace("DEVICE_DISPATCH_LOCK", "STATS_LOCK")
    assert _ids(other) == ["GL012", "GL012"]  # device_put + device_get


def test_device_dispatch_context_holds_the_dispatch_lock():
    """``with device_dispatch(site)`` is a hold of the process-wide
    dispatch lock (or of ``lock=``): device verbs under it stay exempt,
    any other blocking call under it is GL012, and it joins the
    lock-order graph under the lock's own id."""
    src = """
import time
import jax
from milnce_tpu.serving.engine import device_dispatch

def run(fn, x, sh):
    with device_dispatch("index.topk", rows=1) as hold:
        out = jax.device_get(fn(jax.device_put(x, sh)))
    return out

def bad(fn, x):
    with device_dispatch("index.topk"):
        time.sleep(1.0)
"""
    findings = [f for f in lint_source(src) if not f.suppressed]
    assert [f.rule.id for f in findings] == ["GL012"]
    assert "engine:DEVICE_DISPATCH_LOCK" in findings[0].message
    own = src.replace('device_dispatch("index.topk"):',
                      'device_dispatch("index.topk", lock=STATS_LOCK):'
                      ).replace("import time", "import time\nimport "
                                "threading\nSTATS_LOCK = threading.Lock()")
    msgs = [f.message for f in lint_source(own) if not f.suppressed]
    assert len(msgs) == 1 and "STATS_LOCK" in msgs[0]


# ---------------------------------------------------------------------------
# GL000 stale suppressions + the --no-concurrency contract
# ---------------------------------------------------------------------------

def test_stale_suppression_is_gl000():
    findings = lint_source("y = 1  # graftlint: disable=GL004(was real once)\n")
    assert [f.rule.id for f in findings] == ["GL000"]
    assert "stale" in findings[0].message


def test_matching_suppression_is_not_stale():
    src = ("import jax.numpy as jnp\n"
           "pad = jnp.asarray(0.5)  # graftlint: disable=GL004(audited)\n")
    findings = lint_source(src)
    assert [f.rule.id for f in findings] == ["GL004"]
    assert findings[0].suppressed


def test_pass3_suppressions_not_stale_under_no_concurrency():
    """With the concurrency pass off, a GL010 suppression is
    unevaluated, not stale — staleness only judges rules that ran."""
    src = _SHARED_WRITE.replace(
        "        self.calls += 1",
        "        self.calls += 1  # graftlint: disable=GL010(audited)")
    with_pass = lint_source(src)
    assert [f.rule.id for f in with_pass] == ["GL010"]
    assert with_pass[0].suppressed
    without = lint_source(src, concurrency=False)
    assert without == []


def test_gl011_suppression_never_judged_stale_under_narrowed_scope():
    """A cross-module cycle's audited GL011 suppression must survive a
    narrowed-scope lint (the partner module's edge isn't in scope, so
    absence-of-cycle is not evidence of staleness)."""
    src = """
import threading

A = threading.Lock()

def one():
    # graftlint: disable=GL011(cycle partner lives in another module)
    with A:
        pass
"""
    assert [f.rule.id for f in lint_source(src)] == []


def test_cli_no_concurrency_skips_gl010(tmp_path):
    bad = tmp_path / "racy.py"
    bad.write_text(_SHARED_WRITE)
    cli = [sys.executable, os.path.join(_REPO, "scripts", "graft_lint.py"),
           "--check", "--no-trace", "--report", "", str(bad)]
    proc = subprocess.run(cli, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1 and "GL010" in proc.stdout, proc.stdout
    proc = subprocess.run(cli + ["--no-concurrency"], capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr


# ---------------------------------------------------------------------------
# guard-map CLI (the SERVING.md "Threading model" source)
# ---------------------------------------------------------------------------

def test_guard_map_markdown_covers_the_serving_mesh():
    from milnce_tpu.analysis.concurrency import guard_map_markdown

    table = guard_map_markdown([os.path.join(_REPO, "milnce_tpu",
                                             "serving"),
                                os.path.join(_REPO, "milnce_tpu", "obs")])
    # the inferred guard map names the classes and disciplines the
    # threading-model doc is generated from
    assert "`engine.InferenceEngine`" in table
    assert "`batcher.DynamicBatcher`" in table
    assert "`_calls`" in table
    assert "guarded by `InferenceEngine._stats_lock`" in table
    assert "write-once in `__init__`" in table
