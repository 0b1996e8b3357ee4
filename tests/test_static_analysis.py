"""Tier-1 gate + self-tests for the project invariant analyzer
(kubernetes_tpu/analysis/, docs/ANALYSIS.md).

Three layers:

- fixture corpus: every checker must flag its known-bad snippets (the
  recorded incident patterns, seeded) and pass its known-good twins;
- the tree gate: `analyze()` over the real package reports zero findings
  and zero stale allowlist entries — this is what makes the analyzer a
  floor under every future PR;
- the CLI contract: `python -m kubernetes_tpu.analysis` exits 0 on the
  tree and nonzero (with --json detail) on a tree seeded with violations.
"""

from __future__ import annotations

import json
import subprocess
import sys
import textwrap

import pytest

from kubernetes_tpu.analysis import (ALLOWLIST, Allow, all_checkers, analyze,
                                     check_source, checker_by_id,
                                     validate_allowlist)
from kubernetes_tpu.analysis.metrics_discipline import (Declaration,
                                                        MetricsDisciplineChecker)


def _rules(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# fixture corpus: index-dtype
# ---------------------------------------------------------------------------


class TestIndexDtypeFixtures:
    def test_flags_bad_producers(self):
        bad = textwrap.dedent("""
            import jax.numpy as jnp
            def f(x, idx, dirty):
                a = jnp.arange(5)                     # bare arange
                b = jnp.argmax(x, axis=0)             # uncast argmax
                c = jnp.asarray(idx)                  # index vec, no dtype
                d = jnp.asarray(sorted(dirty))        # ditto through sorted
                return a, b, c, d
        """)
        fs = check_source(checker_by_id("index-dtype"), bad)
        assert _rules(fs) == ["arange-dtype", "argmax-cast",
                              "asarray-index-dtype"]
        assert len(fs) == 4
        assert {f.line for f in fs} == {4, 5, 6, 7}

    def test_passes_pinned_producers(self):
        good = textwrap.dedent("""
            import jax.numpy as jnp
            def f(x, idx, dirty):
                a = jnp.arange(5, dtype=jnp.int32)
                b = jnp.argmax(x, axis=0).astype(jnp.int32)
                c = jnp.asarray(idx, jnp.int32)
                d = jnp.asarray(sorted(dirty), dtype=jnp.int32)
                e = jnp.asarray(x)     # not an index-named vector: exempt
                return a, b, c, d, e
        """)
        assert check_source(checker_by_id("index-dtype"), good) == []

    def test_string_parens_do_not_confuse_the_scan(self):
        """The old regex guard's _call_text was string-literal-naive: a ')'
        inside a string ended its paren matching. The AST checker must see
        through it both ways."""
        tricky_good = textwrap.dedent("""
            import jax.numpy as jnp
            def f():
                msg = "jnp.arange(8)"     # a string, not a call
                return jnp.arange(8, dtype=jnp.int32), msg
        """)
        assert check_source(checker_by_id("index-dtype"), tricky_good) == []
        tricky_bad = textwrap.dedent("""
            import jax.numpy as jnp
            def f():
                note = ") dtype= :)"      # old parser would see this text
                return jnp.arange(8), note
        """)
        fs = check_source(checker_by_id("index-dtype"), tricky_bad)
        assert _rules(fs) == ["arange-dtype"]

    def test_argmax_cast_is_statement_scoped(self):
        mixed = textwrap.dedent("""
            import jax.numpy as jnp
            def f(x):
                i = jnp.argmax(x)          # bad: cast happens a line later
                i = i.astype(jnp.int32)
                return i
        """)
        fs = check_source(checker_by_id("index-dtype"), mixed)
        assert _rules(fs) == ["argmax-cast"]


# ---------------------------------------------------------------------------
# fixture corpus: lock-discipline
# ---------------------------------------------------------------------------


BAD_APISERVER = textwrap.dedent("""
    import threading
    class Server:
        def do_POST(self):                       # no write lock, no delegate
            body = self._read_body()
            self.store.pods[body["uid"]] = body
        def _broadcast(self, kind, event):
            with self._lock:
                for q in self._watchers[kind]:   # fanout BEFORE the append
                    q.put(event)
                self.persistence.append(event)
        def _wal_status(self, rec):
            self.persistence.append(rec)         # append outside any lock
        def do_DELETE(self):
            with self._write_lock:
                body = self._read_body()         # blocking read under lock
""")

GOOD_APISERVER = textwrap.dedent("""
    import threading
    class Server:
        def do_POST(self):
            body = self._read_body()             # read OUTSIDE the lock
            with self._write_lock:
                self.store.pods[body["uid"]] = body
        def do_PUT(self):
            self.upsert(self._read_body())       # delegate serializes
        def upsert(self, rec):
            with self._write_lock:
                self.leases[rec["name"]] = rec
        def _broadcast(self, kind, event):
            with self._lock:
                self.persistence.append(event)   # durable BEFORE fanout
                for q in self._watchers[kind]:
                    q.put(event)
""")


class TestLockDisciplineFixtures:
    def test_flags_all_four_rules(self):
        fs = check_source(checker_by_id("lock-discipline"), BAD_APISERVER)
        assert _rules(fs) == ["no-blocking-read-under-lock",
                              "verb-write-lock", "wal-before-fanout",
                              "wal-under-broadcast-lock"]

    def test_passes_disciplined_server(self):
        assert check_source(checker_by_id("lock-discipline"),
                            GOOD_APISERVER) == []

    def test_directly_nested_withs_hold_both_locks(self):
        """Regression (PR 7 review): a `with` as the DIRECT first statement
        of another `with`'s body must inherit the outer lock — correct
        code like write_lock-then-broadcast-lock used to false-positive."""
        nested_good = textwrap.dedent("""
            class Server:
                def commit(self, event):
                    with self._write_lock:
                        with self._lock:
                            self.persistence.append(event)
                            for q in self._watchers["pods"]:
                                q.put(event)
        """)
        assert check_source(checker_by_id("lock-discipline"),
                            nested_good) == []

    def test_duplicate_function_names_each_get_scanned(self):
        """Regression (PR 7 review): two defs sharing a name (apiserver.py
        has upsert_lease on BOTH APIServer and HTTPClientset) must each be
        analyzed — the buggy version kept only the last one, silently
        skipping the server-side locking."""
        dup = textwrap.dedent("""
            class Server:
                def upsert_lease(self, rec):
                    self.persistence.append(rec)     # VIOLATION: no lock
            class Client:
                def upsert_lease(self, rec):
                    return self._call("PUT", rec)    # clean REST wrapper
        """)
        fs = check_source(checker_by_id("lock-discipline"), dup)
        assert _rules(fs) == ["wal-under-broadcast-lock"]
        assert len(fs) == 1 and fs[0].line == 4

    def test_scope_is_apiserver_and_wal(self):
        c = checker_by_id("lock-discipline")
        assert c.applies_to("core/apiserver.py")
        assert c.applies_to("core/wal.py")
        assert not c.applies_to("core/scheduler.py")

    def test_flags_metrics_render_under_write_lock(self):
        """PR 8 rule: /metrics exposition must never hold the write lock —
        a scrape serialized against the write plane stalls every bind for
        the whole render (ROADMAP: /metrics/resources contention)."""
        bad = textwrap.dedent("""
            class Server:
                def do_GET(self):
                    with self._write_lock:
                        body = self.expose_metrics()
                def expose_metrics(self):
                    return ""
        """)
        fs = check_source(checker_by_id("lock-discipline"), bad)
        assert "no-render-under-write-lock" in _rules(fs)

    def test_render_outside_write_lock_is_clean(self):
        good = textwrap.dedent("""
            class Server:
                def do_GET(self):
                    body = self.expose_metrics()   # no lock held: fine
                    with self._lock:
                        n = len(self._watchers)    # broadcast lock ≠ write
                def expose_metrics(self):
                    return ""
        """)
        fs = check_source(checker_by_id("lock-discipline"), good)
        assert "no-render-under-write-lock" not in _rules(fs)


# ---------------------------------------------------------------------------
# fixture corpus: lock-discipline, replication rules (PR 9)
# ---------------------------------------------------------------------------


BAD_REPLICATION = textwrap.dedent("""
    class Follower:
        def apply_frame(self, rec):                  # no write lock taken
            with self._lock:
                for q in self._watchers["pods"]:     # fanout BEFORE append
                    q.put(rec)
                self.persistence.append(rec)
        def _wal_status(self, rec):
            self._repl_append(rec)                   # frame append, no lock
        def _ship(self, st):
            with self._lock:
                self.wfile.write(b"x")               # send under lock
                st.sock.sendall(b"y")                # ditto
""")

GOOD_REPLICATION = textwrap.dedent("""
    class Follower:
        def apply_frame(self, rec):
            with self._write_lock:
                with self._lock:
                    self.persistence.append(rec)     # durable FIRST
                    for q in self._watchers["pods"]:
                        q.put(rec)
        def _wal_status(self, rec):
            with self._lock:
                self._repl_append(rec)               # caller holds the lock
        def _ship(self, st):
            with self._lock:
                frames = list(st.pending)            # snapshot under lock
            for data in frames:
                self.wfile.write(data)               # send OUTSIDE any lock
""")


class TestReplicationLockFixtures:
    def test_flags_replication_violations(self):
        fs = check_source(checker_by_id("lock-discipline"), BAD_REPLICATION)
        assert _rules(fs) == ["no-blocking-send-under-lock",
                              "repl-apply-write-lock",
                              "wal-before-fanout",
                              "wal-under-broadcast-lock"]
        # both send sites (wfile.write AND sendall) are individually flagged
        assert sum(1 for f in fs
                   if f.rule == "no-blocking-send-under-lock") == 2

    def test_passes_disciplined_follower(self):
        assert check_source(checker_by_id("lock-discipline"),
                            GOOD_REPLICATION) == []

    def test_repl_append_inside_primitive_is_exempt(self):
        """The frame-append primitive OWNS the raw persistence.append; its
        contract (caller holds the broadcast lock) is enforced at call
        sites, not inside it."""
        primitive = textwrap.dedent("""
            class Server:
                def _repl_append(self, rec):
                    self.persistence.append(rec)     # exempt: the primitive
                def _broadcast(self, event):
                    with self._lock:
                        self._repl_append(event)     # call site: locked
        """)
        assert check_source(checker_by_id("lock-discipline"),
                            primitive) == []

    def test_scope_covers_replication_module(self):
        c = checker_by_id("lock-discipline")
        assert c.applies_to("replication/follower.py")
        assert c.applies_to("kubernetes_tpu/replication/follower.py")
        assert not c.applies_to("core/scheduler.py")


# ---------------------------------------------------------------------------
# fixture corpus: lock-discipline — watch-cache read plane (PR 10)
# ---------------------------------------------------------------------------


BAD_WATCHCACHE = textwrap.dedent("""
    class Server:
        def do_summary(self):
            with self._write_lock:                       # read plane must
                return self.watch_cache["pods"].read_summary()  # not be here
        def do_list(self):
            with self._write_lock:
                return self.watch_cache["pods"].list_wire()
        def _broadcast(self, event):
            with self._lock:
                self.watch_cache["pods"].note_event(1, "ADDED", event)
                self._repl_append(event)                 # append AFTER cache
        def _recover_seed(self, objs):
            self.watch_cache["pods"].reinstall(objs, 0)  # outside the lock
""")

GOOD_WATCHCACHE = textwrap.dedent("""
    class Server:
        def do_summary(self):
            return self.watch_cache["pods"].read_summary()   # own lock only
        def do_resources(self):
            return self.watch_cache["pods"].render_resources()
        def _broadcast(self, event):
            with self._lock:
                self._repl_append(event)                 # durable first...
                self._fan_event("pods", event, b"")      # ...then cache+fan
        def _fan_event(self, kind, event, data):
            self.watch_cache[kind].note_event(1, "ADDED", event)  # primitive
            for w in self._watchers[kind]:
                w.q.put(data)
""")


class TestWatchCacheLockFixtures:
    def test_flags_watchcache_violations(self):
        fs = check_source(checker_by_id("lock-discipline"), BAD_WATCHCACHE)
        assert _rules(fs) == ["no-read-serving-under-write-lock"]
        # two reads under the write lock + the mutation-before-append +
        # the unlocked reinstall are each individually flagged
        assert len(fs) == 4

    def test_passes_disciplined_watchcache(self):
        """The fanout primitive owns the raw note_event (caller-holds-lock
        contract, enforced at its call sites) — the real apiserver shape
        passes clean."""
        assert check_source(checker_by_id("lock-discipline"),
                            GOOD_WATCHCACHE) == []

    def test_fan_event_call_outside_lock_flagged(self):
        bad = textwrap.dedent("""
            class Server:
                def _broadcast(self, event):
                    with self._lock:
                        self._repl_append(event)
                    self._fan_event("pods", event, b"")  # lock released!
                def _fan_event(self, kind, event, data):
                    self.watch_cache[kind].note_event(1, "ADDED", event)
        """)
        fs = check_source(checker_by_id("lock-discipline"), bad)
        assert "no-read-serving-under-write-lock" in _rules(fs)

    def test_scope_covers_watchcache_module(self):
        c = checker_by_id("lock-discipline")
        assert c.applies_to("core/watchcache.py")
        assert c.applies_to("kubernetes_tpu/core/watchcache.py")


# ---------------------------------------------------------------------------
# fixture corpus: lock-discipline — paged-LIST continuation path (PR 11)
# ---------------------------------------------------------------------------


BAD_CONTINUATION = textwrap.dedent("""
    class Server:
        def serve_page(self, limit, last_key):
            with self._write_lock:                        # continuation
                objs = self.watch_cache["pods"].list_page(limit, last_key)
                token = mint_continue(1, last_key, "e")   # minted under it
            return objs, token
""")

GOOD_CONTINUATION = textwrap.dedent("""
    class Server:
        def serve_page(self, limit, last_key):
            objs = self.watch_cache["pods"].list_page(limit, last_key)
            token = mint_continue(1, last_key, "e")       # lock-free mint
            return objs, token
""")


class TestContinuationLockFixtures:
    def test_flags_page_serving_and_minting_under_write_lock(self):
        """The continuation-serving path is a READ: a 50k-node paged list
        serialized against the bind plane stalls it once per page."""
        fs = check_source(checker_by_id("lock-discipline"), BAD_CONTINUATION)
        assert _rules(fs) == ["no-read-serving-under-write-lock"]
        assert len(fs) == 2   # the page serve AND the token mint

    def test_passes_lock_free_continuation(self):
        assert check_source(checker_by_id("lock-discipline"),
                            GOOD_CONTINUATION) == []

    def test_scope_covers_hollow_plane(self):
        c = checker_by_id("lock-discipline")
        assert c.applies_to("hollow/plane.py")
        assert c.applies_to("kubernetes_tpu/hollow/plane.py")


# ---------------------------------------------------------------------------
# fixture corpus: jit-purity
# ---------------------------------------------------------------------------


class TestJitPurityFixtures:
    def test_flags_impure_jit_functions(self):
        bad = textwrap.dedent("""
            import jax, time
            from functools import partial
            CALLS = 0
            @partial(jax.jit, static_argnames=("k",))
            def kernel(x, k):
                global CALLS
                CALLS += 1                 # baked in at trace time
                print("tracing", x)        # host effect under trace
                t = time.perf_counter()    # host clock under trace
                return x * k
            def build(state, cfg):
                def step(s):
                    cfg.calls = 1          # attr mutation under trace
                    return s + 1
                return jax.jit(step)
        """)
        fs = check_source(checker_by_id("jit-purity"), bad)
        assert _rules(fs) == ["no-attr-assign", "no-global-mutation",
                              "no-impure-call"]
        assert sum(f.rule == "no-impure-call" for f in fs) == 2

    def test_passes_pure_kernels(self):
        good = textwrap.dedent("""
            import jax
            import jax.numpy as jnp
            from functools import partial
            @partial(jax.jit, static_argnames=("k",))
            def kernel(x, k):
                jax.debug.print("ok {}", x)   # traced debugging is fine
                return jnp.cumsum(x) * k
            def host_driver(state):
                state.calls = 1               # host code may mutate freely
                import time
                return time.perf_counter()
        """)
        assert check_source(checker_by_id("jit-purity"), good) == []

    def test_transitive_helpers_are_traced_too(self):
        """A helper called from a jitted function is traced like its
        caller — impurity there is the same bug one stack frame down."""
        bad = textwrap.dedent("""
            import jax
            @jax.jit
            def kernel(x):
                return _helper(x)
            def _helper(x):
                print("traced!")       # impure, reached through kernel
                return x + 1
            def _host_only(x):
                print("fine")          # never reaches a jit
                return x
        """)
        fs = check_source(checker_by_id("jit-purity"), bad)
        assert [f.rule for f in fs] == ["no-impure-call"]
        assert fs[0].line == 7

    def test_flags_donated_buffer_reuse(self):
        bad = textwrap.dedent("""
            import jax
            def session(carry, feats):
                step = jax.jit(lambda c, f: c, donate_argnums=(0,))
                step = jax.jit(_impl, donate_argnums=(0,))
                out = step(carry, feats)
                return out + carry.total     # carry's buffer was donated
            def _impl(c, f):
                return c
        """)
        fs = check_source(checker_by_id("jit-purity"), bad)
        assert any(f.rule == "donated-buffer-reuse" for f in fs)

    def test_donation_rebind_is_clean(self):
        good = textwrap.dedent("""
            import jax
            def session(carry, feats):
                step = jax.jit(_impl, donate_argnums=(0,))
                carry = step(carry, feats)   # rebound: later reads see new
                return carry.total
            def _impl(c, f):
                return c
        """)
        assert check_source(checker_by_id("jit-purity"), good) == []


# ---------------------------------------------------------------------------
# fixture corpus: thread-hygiene
# ---------------------------------------------------------------------------


class TestThreadHygieneFixtures:
    def test_flags_unjoined_nondaemon_threads(self):
        bad = textwrap.dedent("""
            import threading
            class Pump:
                def start(self):
                    self._t = threading.Thread(target=self._run)
                    self._t.start()
                    threading.Thread(target=self._aux).start()
        """)
        fs = check_source(checker_by_id("thread-hygiene"), bad)
        assert len(fs) == 2
        assert _rules(fs) == ["daemon-or-joined"]

    def test_passes_daemon_joined_and_pooled(self):
        good = textwrap.dedent("""
            import threading
            class Pump:
                def start(self):
                    self._t = threading.Thread(target=self._run)
                    self._t.start()
                    threading.Thread(target=self._aux, daemon=True).start()
                    w = threading.Thread(target=self._w)
                    self._threads.append(w)
                    self._threads.append(threading.Thread(target=self._v))
                def close(self):
                    self._t.join(timeout=2)
                    for t in self._threads:
                        t.join(timeout=2)
        """)
        assert check_source(checker_by_id("thread-hygiene"), good) == []


# ---------------------------------------------------------------------------
# fixture corpus: metrics-discipline
# ---------------------------------------------------------------------------


DECLS = {
    "hits": Declaration("hits", "Counter", "scheduler_hits_total",
                        ("result",), 10),
    "depth": Declaration("depth", "Gauge", "scheduler_depth", (), 11),
    "latency": Declaration("latency", "Histogram", "scheduler_latency",
                           ("kind",), 12),
}


class TestMetricsDisciplineFixtures:
    def _check(self, src):
        return check_source(MetricsDisciplineChecker(declarations=DECLS), src)

    def test_flags_undeclared_mismatch_and_arity(self):
        bad = textwrap.dedent("""
            class S:
                def go(self):
                    self.metrics.misses.inc()              # undeclared
                    self.metrics.depth.inc()               # Gauge via inc
                    self.metrics.hits.inc("ok", "extra")   # arity 2 != 1
                    self.metrics.latency.observe(0.5)      # arity 1 != 2
        """)
        fs = self._check(bad)
        assert _rules(fs) == ["label-arity", "metric-verb-mismatch",
                              "undeclared-metric"]
        assert sum(f.rule == "label-arity" for f in fs) == 2

    def test_resolves_local_aliases(self):
        bad = textwrap.dedent("""
            class S:
                def go(self):
                    m = self.metrics
                    m.misses.inc()                    # undeclared via alias
                    h = self.metrics.latency
                    h.observe(0.5)                    # arity via alias
        """)
        fs = self._check(bad)
        assert _rules(fs) == ["label-arity", "undeclared-metric"]

    def test_passes_disciplined_usage(self):
        good = textwrap.dedent("""
            class S:
                def go(self, n):
                    self.metrics.hits.inc("ok")
                    self.metrics.hits.inc("ok", value=n)
                    self.metrics.depth.set(float(n))
                    h = self.metrics.latency
                    h.observe(0.5, "bind")
                    self.other.inc("unrelated", "object", "calls")
        """)
        assert self._check(good) == []

    def test_label_cardinality_bound(self):
        over = textwrap.dedent("""
            class SchedulerMetrics:
                def __init__(self):
                    r = self.registry.register
                    self.wide = r(Counter(
                        "scheduler_wide_total", "too many dims.",
                        ("a", "b", "c", "d")))
        """)
        fs = check_source(MetricsDisciplineChecker(declarations=DECLS), over,
                          path="core/metrics.py")
        assert _rules(fs) == ["label-cardinality"]

    def test_real_declarations_parse(self):
        from kubernetes_tpu.analysis.metrics_discipline import (
            parse_declarations)
        from kubernetes_tpu.analysis.base import PKG_ROOT
        decls = parse_declarations((PKG_ROOT / "core/metrics.py").read_text())
        assert len(decls) > 50
        assert decls["schedule_attempts"].kind == "Counter"
        assert decls["schedule_attempts"].labels == ("result", "profile")
        assert decls["pending_pods"].kind == "Gauge"
        assert all(d.labels is None or len(d.labels) <= 3
                   for d in decls.values())


# ---------------------------------------------------------------------------
# fixture corpus: span-discipline (PR 8 telemetry contract)
# ---------------------------------------------------------------------------


class TestSpanDisciplineFixtures:
    def test_flags_unended_and_unguarded_starts(self):
        bad = textwrap.dedent("""
            class S:
                def leak(self, pod):
                    sp = self.tracer.start_span("api.bind", self.ctx)
                    self.commit(pod)               # never ended: leaks
                def unguarded(self, pod):
                    sp = self.tracer.start_span("api.bind", self.ctx)
                    self.commit(pod)               # raises -> end skipped
                    self.tracer.end(sp)
        """)
        fs = check_source(checker_by_id("span-discipline"), bad)
        assert _rules(fs) == ["span-end-unguarded", "span-unended"]

    def test_passes_with_scoped_and_finally_ended_spans(self):
        good = textwrap.dedent("""
            class S:
                def scoped(self, pod):
                    with self.tracer.span("api.bind", self.ctx):
                        self.commit(pod)
                def guarded(self, pod):
                    sp = self.tracer.start_span("api.bind", self.ctx)
                    try:
                        self.commit(pod)
                    finally:
                        self.tracer.end(sp)
                def method_form(self, pod):
                    sp = self.tracer.start_span("api.bind", self.ctx)
                    try:
                        self.commit(pod)
                    finally:
                        sp.end()
                def retro(self, pod):
                    self.tracer.record("api.bind", self.ctx, 0.1)  # complete
        """)
        assert check_source(checker_by_id("span-discipline"), good) == []

    def test_flags_span_and_metric_calls_in_jit_reachable_code(self):
        """Composes with the jit-purity walker: a tracer/metrics call one
        helper below a jitted kernel is the same trace-time-bake bug."""
        bad = textwrap.dedent("""
            import jax
            @jax.jit
            def kernel(x, self):
                return _helper(x, self)
            def _helper(x, self):
                self.tracer.record("device.wait", self.ctx, 0.1)
                self.metrics.batch_size.observe(4)
                return x
        """)
        fs = check_source(checker_by_id("span-discipline"), bad)
        assert _rules(fs) == ["span-in-jit"]
        assert len(fs) == 2

    def test_the_collectors_callback_and_the_inbox_stamp_stay_on_the_host(self):
        """What PR 36 stamps where the work happens (the collector clock's
        callback booking `gc.pause`, the park's stamp and the drain's
        observation of `scheduler_inbox_oldest_wait_seconds`, the ledger's
        `collecting`) is reachable from no jitted entry, and the rule would
        say so if it were: the same observation one helper below a jitted
        function is a finding."""
        import ast
        import os

        import kubernetes_tpu
        from kubernetes_tpu.analysis.jit_purity import jit_reachable_functions

        pkg = os.path.dirname(kubernetes_tpu.__file__)
        stamped = {"core/spans.py": {"_callback", "collecting"},
                   "core/collector.py": {"acquire"},
                   "core/scheduler.py": {"dispatch", "drain_event_inbox",
                                         "_threaded", "schedule_one"},
                   "models/tpu_scheduler.py": {"_dispatch_attrs"}}
        checker = checker_by_id("span-discipline")
        for rel, names in stamped.items():
            with open(os.path.join(pkg, rel)) as f:
                src = f.read()
            tree = ast.parse(src)
            defined = {n.name for n in ast.walk(tree)
                       if isinstance(n, ast.FunctionDef)}
            assert names <= defined, (rel, names - defined)
            reachable = {fn.name for fn in jit_reachable_functions(tree)}
            assert not names & reachable, (rel, names & reachable)
            assert check_source(checker, src) == [], rel
        bad = textwrap.dedent("""
            import jax
            @jax.jit
            def kernel(x, self):
                return _drain(x, self)
            def _drain(x, self):
                self.metrics.inbox_oldest_wait.observe(0.1)
                return x
        """)
        assert _rules(check_source(checker, bad)) == ["span-in-jit"]

    def test_host_side_span_and_metric_calls_are_clean(self):
        good = textwrap.dedent("""
            import jax
            @jax.jit
            def kernel(x):
                return x + 1
            def host_commit(self, batch):
                self.tracer.record("host.commit", self.ctx, 0.1)
                self.metrics.batch_size.observe(len(batch))
                return kernel(batch)
        """)
        assert check_source(checker_by_id("span-discipline"), good) == []


# ---------------------------------------------------------------------------
# fixture corpus: hint-freshness
# ---------------------------------------------------------------------------


class TestHintFreshnessFixtures:
    """Cache NodeInfo-accounting mutations must be on the score-hint
    invalidation call graph (ISSUE 12: a mutation the journal/fences never
    see would silently stale a live hint)."""

    def test_flags_unfenced_mutation(self):
        bad = textwrap.dedent("""
            class S:
                def sneaky_rebalance(self, pod):
                    # moves accounting with no journal record, no fence
                    self.cache.forget_pod(pod)
                    self.cache.assume_pod(pod)
        """)
        fs = check_source(checker_by_id("hint-freshness"), bad)
        assert _rules(fs) == ["accounting-outside-invalidation-graph"]
        assert len(fs) == 2

    def test_passes_journaled_mutation(self):
        good = textwrap.dedent("""
            class S:
                def on_event(self, kind, new):
                    self._record_pod_event(kind, None, new)
                    self.cache.add_pod(new)
        """)
        assert check_source(checker_by_id("hint-freshness"), good) == []

    def test_passes_fence_counter_bump(self):
        good = textwrap.dedent("""
            class S:
                def unwind(self, pod):
                    self.state_unwinds += 1
                    self.cache.forget_pod(pod)
        """)
        assert check_source(checker_by_id("hint-freshness"), good) == []

    def test_passes_hint_cache_call(self):
        good = textwrap.dedent("""
            class S:
                def conflict(self, pod, node):
                    self.cache.forget_pod(pod)
                    self._hints.note_conflict(node)
        """)
        assert check_source(checker_by_id("hint-freshness"), good) == []

    def test_caller_direction_credits_the_slice(self):
        """The process_one → scheduling_cycle shape: the assume lives one
        frame below the attempt-counter bump — the SLICE has the sink."""
        good = textwrap.dedent("""
            class S:
                def process_one(self, qpi):
                    self.attempts += 1
                    self.scheduling_cycle(qpi)
                def scheduling_cycle(self, qpi):
                    self.cache.assume_pod(qpi.pod)
        """)
        assert check_source(checker_by_id("hint-freshness"), good) == []

    def test_callee_direction_credits_the_slice(self):
        good = textwrap.dedent("""
            class S:
                def commit(self, pod):
                    self.cache.assume_pod(pod)
                    self.note_it()
                def note_it(self):
                    self.attempts += 1
        """)
        assert check_source(checker_by_id("hint-freshness"), good) == []

    def test_snapshot_whatif_mutations_exempt(self):
        """snapshot.assume_pod is a gang-simulation what-if, not cache
        accounting — matched on the `cache` base, so exempt."""
        good = textwrap.dedent("""
            class S:
                def simulate(self, pod):
                    self.snapshot.assume_pod(pod)
                    self.snapshot.forget_pod(pod)
        """)
        assert check_source(checker_by_id("hint-freshness"), good) == []

    def test_unrelated_caller_does_not_credit(self):
        """A sink-holding function that never reaches the mutator must not
        launder it."""
        bad = textwrap.dedent("""
            class S:
                def elsewhere(self):
                    self.attempts += 1
                def sneaky(self, pod):
                    self.cache.forget_pod(pod)
        """)
        fs = check_source(checker_by_id("hint-freshness"), bad)
        assert len(fs) == 1 and fs[0].line == 6

    def test_duplicate_method_names_both_scanned(self):
        """lock-discipline's lesson, re-learned here in review: a Handle
        delegate sharing a Scheduler method's NAME must not shadow the
        real def — the SECOND def's unfenced mutation is a finding."""
        bad = textwrap.dedent("""
            class Handle:
                def reject_waiting_pod(self, uid):
                    return self._scheduler.lookup(uid)
            class S:
                def reject_waiting_pod(self, uid):
                    self.cache.forget_pod(uid)   # unfenced, 2nd def
        """)
        fs = check_source(checker_by_id("hint-freshness"), bad)
        assert len(fs) == 1 and fs[0].line == 7


# ---------------------------------------------------------------------------
# fixture corpus: shed-discipline (overload plane, PR 14)
# ---------------------------------------------------------------------------


BAD_SHED = textwrap.dedent("""
    class Handler:
        def do_POST(self):
            ticket = None
            with server._write_lock:
                # admission under the very lock it exists to protect
                ticket = self._flow_admit("POST")
                code, obj = self._post_locked()
            if ticket is None:
                # 429 with no Retry-After: the shed contract broken
                self._json(429, {"error": "TooManyRequests"})
""")

GOOD_SHED = textwrap.dedent("""
    class Handler:
        def do_POST(self):
            ticket = self._flow_admit("POST")
            if ticket is None:
                return  # 429 + Retry-After already sent by _flow_admit
            try:
                with server._write_lock:
                    code, obj = self._post_locked()
            finally:
                server.flowcontrol.release(ticket)
            self._json(code, obj)

        def _flow_admit(self, method):
            ticket = server.flowcontrol.admit("workload", "ns")
            if ticket is None:
                self._json(429, {"error": "TooManyRequests"},
                           retry_after=1)
            return ticket
""")


class TestShedDisciplineFixtures:
    def test_flags_shed_violations(self):
        fs = check_source(checker_by_id("shed-discipline"), BAD_SHED)
        assert _rules(fs) == ["429-without-retry-after",
                              "shed-under-write-lock"]

    def test_passes_disciplined_shed_path(self):
        assert check_source(checker_by_id("shed-discipline"),
                            GOOD_SHED) == []

    def test_flowcontrol_admit_under_lock_flagged(self):
        bad = textwrap.dedent("""
            class Handler:
                def do_PUT(self):
                    with server._write_lock:
                        t = server.flowcontrol.admit("workload", "ns")
        """)
        fs = check_source(checker_by_id("shed-discipline"), bad)
        assert _rules(fs) == ["shed-under-write-lock"]

    def test_unrelated_admit_not_flagged(self):
        good = textwrap.dedent("""
            class Handler:
                def do_PUT(self):
                    with server._write_lock:
                        self.gatekeeper.admit(pod)  # not flow control
        """)
        assert check_source(checker_by_id("shed-discipline"), good) == []

    def test_retry_after_literal_outside_backoff_flagged(self):
        """A client module growing its own Retry-After parsing beside the
        shared backoff stack is the rot this rule exists for."""
        bad = textwrap.dedent("""
            def my_retry_loop(call):
                try:
                    return call()
                except Exception as e:
                    wait = float(e.headers.get("Retry-After", 1))
                    time.sleep(wait)
        """)
        fs = check_source(checker_by_id("shed-discipline"), bad,
                          path="shard/member.py")
        assert _rules(fs) == ["retry-after-parse-outside-backoff"]

    def test_retry_after_literal_in_seams_exempt(self):
        src = 'HEADER = "Retry-After"\n'
        for seam in ("core/backoff.py", "core/apiserver.py",
                     "core/flowcontrol.py"):
            assert check_source(checker_by_id("shed-discipline"), src,
                                path=seam) == []

    def test_scope(self):
        c = checker_by_id("shed-discipline")
        assert c.applies_to("core/apiserver.py")
        assert c.applies_to("shard/member.py")


class TestShardingDisciplineFixtures:
    """ISSUE 15 mesh-first plane: a bare jax.jit inside the sharded-state
    seam hands back GSPMD-chosen placements and silently retraces the
    session kernel on the next dispatch."""

    def test_bare_jit_with_sharded_state_param_flagged(self):
        bad = textwrap.dedent("""
            import jax

            def sharded_scatter(sharded_state, idx, rows):
                fn = jax.jit(scatter_impl)
                return fn(sharded_state, idx, rows)
        """)
        fs = check_source(checker_by_id("sharding-discipline"), bad)
        assert _rules(fs) == ["bare-jit-on-sharded-state"]

    def test_bare_jit_near_sharded_state_callsite_flagged(self):
        bad = textwrap.dedent("""
            import jax

            def apply_patch(self, updates, state):
                patch = jax.jit(patch_impl)
                new = self.mirror.patch_rows(updates, sharded_state=state)
                return patch(new)
        """)
        fs = check_source(checker_by_id("sharding-discipline"), bad)
        assert _rules(fs) == ["bare-jit-on-sharded-state"]

    def test_pinned_jit_passes(self):
        good = textwrap.dedent("""
            import jax

            def sharded_scatter(out_shardings, sharded_state, idx, rows):
                fn = jax.jit(scatter_impl, out_shardings=out_shardings)
                return fn(sharded_state, idx, rows)
        """)
        assert check_source(checker_by_id("sharding-discipline"),
                            good) == []

    def test_jit_wrapping_shard_map_exempt(self):
        """shard_map's in/out_specs ARE the placement pin."""
        good = textwrap.dedent("""
            import jax

            def build(mesh, in_specs, out_specs, out_shardings):
                return jax.jit(jax.shard_map(body, mesh=mesh,
                                             in_specs=in_specs,
                                             out_specs=out_specs))
        """)
        assert check_source(checker_by_id("sharding-discipline"),
                            good) == []

    def test_bare_jit_outside_seam_not_flagged(self):
        good = textwrap.dedent("""
            import jax

            def plain_helper(x):
                return jax.jit(lambda a: a + 1)(x)
        """)
        assert check_source(checker_by_id("sharding-discipline"),
                            good) == []

    def test_scope(self):
        c = checker_by_id("sharding-discipline")
        assert c.applies_to("ops/device_state.py")
        assert c.applies_to("parallel/mesh.py")
        assert c.applies_to("models/tpu_scheduler.py")
        assert not c.applies_to("core/apiserver.py")

    def test_shard_map_bodies_join_jit_purity_scope(self):
        """A shard_map-wrapped function is jit-reachable: impure host
        effects inside it are flagged by jit-purity (the ISSUE's 'bodies
        join the jit-purity scan scope')."""
        bad = textwrap.dedent("""
            import time
            import jax

            def body(x):
                time.sleep(1)
                return x

            def build(mesh, specs):
                return jax.shard_map(body, mesh=mesh, in_specs=specs,
                                     out_specs=specs)
        """)
        fs = check_source(checker_by_id("jit-purity"), bad)
        assert any("time" in f.message or "impure" in f.message
                   for f in fs), fs


# ---------------------------------------------------------------------------
# the tree gate + allowlist policy
# ---------------------------------------------------------------------------


def test_tree_runs_clean():
    """The analyzer is a floor: the real package has zero findings (every
    violation the checkers surfaced during PR 7 was fixed, not
    allowlisted) and zero stale allowlist entries."""
    report = analyze()
    assert report.files_scanned > 50
    assert not report.findings, "\n".join(str(f) for f in report.findings)
    assert not report.unused_allows, report.unused_allows


def test_every_checker_registered_and_described():
    checkers = all_checkers()
    ids = sorted(c.id for c in checkers)
    assert ids == ["deschedule-discipline", "eviction-discipline",
                   "hint-freshness", "index-dtype",
                   "jit-purity", "lock-discipline", "metrics-discipline",
                   "reconcile-discipline", "sharding-discipline",
                   "shed-discipline", "span-discipline",
                   "supervision-discipline", "thread-hygiene",
                   "wire-discipline"]
    assert all(c.description for c in checkers)


def test_allowlist_reasons_are_mandatory():
    validate_allowlist(ALLOWLIST)  # current entries all carry reasons
    with pytest.raises(ValueError, match="no reason"):
        validate_allowlist([Allow("index-dtype", "ops/kernel.py", 1, "  ")])


def test_allowlist_suppresses_and_goes_stale():
    import pathlib
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        root = pathlib.Path(td)
        (root / "mod.py").write_text(
            "import jax.numpy as jnp\nix = jnp.arange(4)\n")
        hit = Allow("index-dtype", "mod.py", 2, "fixture: deliberate")
        report = analyze(root=root, allowlist=[hit])
        assert not report.findings and len(report.suppressed) == 1
        stale = Allow("index-dtype", "mod.py", 99, "fixture: wrong line")
        report = analyze(root=root, allowlist=[stale])
        assert len(report.findings) == 1 and report.unused_allows == [stale]


# ---------------------------------------------------------------------------
# fixture corpus: wire-discipline (PR 13)
# ---------------------------------------------------------------------------


class TestWireDiscipline:
    BAD_FANOUT = (
        "import json\n"
        "class S:\n"
        "    def _broadcast(self, event):\n"
        "        data = (json.dumps(event) + '\\n').encode()\n"
        "        self.fan(data)\n"
        "    def _tail(self, line):\n"
        "        return json.loads(line)\n")

    def test_json_on_hot_surface_flagged(self):
        fs = check_source(checker_by_id("wire-discipline"),
                          self.BAD_FANOUT, path="core/apiserver.py")
        assert {(f.rule, f.line) for f in fs} == {
            ("json-on-wire-surface", 4), ("json-on-wire-surface", 7)}

    def test_aliased_imports_resolved(self):
        aliased = (
            "import json as _j\n"
            "from json import loads as _loads\n"
            "def ship(rec, line):\n"
            "    return _j.dumps(rec), _loads(line)\n")
        fs = check_source(checker_by_id("wire-discipline"),
                          aliased, path="core/wal.py")
        assert len(fs) == 2 and all(
            f.rule == "json-on-wire-surface" for f in fs)

    def test_routing_through_the_seam_is_clean(self):
        good = (
            "from . import wire\n"
            "class S:\n"
            "    def _broadcast(self, event):\n"
            "        self.fan(wire.WireItem(event))\n"
            "    def _meta(self, raw):\n"
            "        return wire.jloads(raw)\n"
            "    def _reply(self, obj, codec):\n"
            "        return wire.encode(obj, codec)\n")
        assert check_source(checker_by_id("wire-discipline"),
                            good, path="core/watchcache.py") == []

    def test_non_hot_modules_and_the_seam_are_out_of_scope(self):
        src = "import json\nx = json.dumps({'a': 1})\n"
        # the codec seam itself IS the json call site
        assert check_source(checker_by_id("wire-discipline"),
                            src, path="core/wire.py") == []
        # harness/bench/debug modules keep plain json freely
        assert check_source(checker_by_id("wire-discipline"),
                            src, path="shard/harness.py") == []

    def test_tree_is_clean(self):
        checker = checker_by_id("wire-discipline")
        report = analyze(checkers=[checker], allowlist=[])
        assert report.findings == [], [str(f) for f in report.findings]


# ---------------------------------------------------------------------------
# fixture corpus: delta-base-under-cache-lock (PR 18)
# ---------------------------------------------------------------------------


class TestDeltaBaseUnderCacheLock:
    def test_unlocked_base_read_in_mint_flagged(self):
        bad = (
            "class WatchCache:\n"
            "    def mint_delta(self, event):\n"
            "        base = self._objects.get(event['key'])\n"
            "        with self._lock:\n"
            "            rv = self._obj_rv.get(event['key'])\n"
            "        return base, rv\n")
        fs = check_source(checker_by_id("wire-discipline"),
                          bad, path="core/watchcache.py")
        assert {(f.rule, f.line) for f in fs} == {
            ("delta-base-under-cache-lock", 3)}

    def test_unlocked_rv_read_in_materialize_flagged(self):
        bad = (
            "class WatchCache:\n"
            "    def materialize_delta(self, rec):\n"
            "        have = self._obj_rv.get(rec['key'])\n"
            "        return have\n")
        fs = check_source(checker_by_id("wire-discipline"),
                          bad, path="core/watchcache.py")
        assert [f.rule for f in fs] == ["delta-base-under-cache-lock"]

    def test_locked_reads_are_clean(self):
        good = (
            "class WatchCache:\n"
            "    def mint_delta(self, event):\n"
            "        with self._lock:\n"
            "            base = self._objects.get(event['key'])\n"
            "            rv = self._obj_rv.get(event['key'])\n"
            "        return base, rv\n"
            "    def materialize_delta(self, rec):\n"
            "        with self._lock:\n"
            "            return dict(self._objects.get(rec['key']) or {})\n")
        assert check_source(checker_by_id("wire-discipline"),
                            good, path="core/watchcache.py") == []

    def test_session_state_in_fanout_path_flagged(self):
        bad = (
            "from . import wire\n"
            "class S:\n"
            "    def _broadcast(self, event):\n"
            "        enc = wire.SessionEncoder()\n"
            "        self.fan(enc.encode(event))\n"
            "    def _route_to(self, st, item):\n"
            "        st.q.put(item.session_bytes(st.enc))\n")
        fs = check_source(checker_by_id("wire-discipline"),
                          bad, path="core/apiserver.py")
        assert {(f.rule, f.line) for f in fs} == {
            ("delta-base-under-cache-lock", 4),
            ("delta-base-under-cache-lock", 7)}

    def test_session_state_on_consumer_thread_is_clean(self):
        good = (
            "from . import wire\n"
            "class Handler:\n"
            "    def _stream(self, kind):\n"
            "        enc = wire.SessionEncoder()\n"
            "        while True:\n"
            "            item = self.q.get()\n"
            "            self.wfile.write(item.session_bytes(enc))\n")
        assert check_source(checker_by_id("wire-discipline"),
                            good, path="core/apiserver.py") == []

    def test_non_delta_functions_out_of_scope(self):
        # snapshot reads elsewhere in the cache (own-lock discipline is
        # the module's business) don't trip the delta rule
        src = (
            "class WatchCache:\n"
            "    def read_summary(self):\n"
            "        return len(self._objects)\n")
        assert check_source(checker_by_id("wire-discipline"),
                            src, path="core/watchcache.py") == []


# ---------------------------------------------------------------------------
# CLI contract
# ---------------------------------------------------------------------------


def _run_cli(*args):
    return subprocess.run(
        [sys.executable, "-m", "kubernetes_tpu.analysis", *args],
        capture_output=True, text=True, timeout=120)


def test_cli_exits_zero_on_the_tree():
    proc = _run_cli()
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 finding(s)" in proc.stdout


def test_cli_exits_nonzero_on_seeded_violations(tmp_path):
    """Acceptance: a seeded bare `jnp.arange` in ops/ and a WAL append
    outside the lock region must fail the scan, with --json detail."""
    ops = tmp_path / "ops"
    ops.mkdir()
    (ops / "bad_kernel.py").write_text(
        "import jax.numpy as jnp\n"
        "def f(n):\n"
        "    return jnp.arange(n)\n")
    core = tmp_path / "core"
    core.mkdir()
    (core / "apiserver.py").write_text(
        "class S:\n"
        "    def _broadcast(self, event):\n"
        "        self.persistence.append(event)\n")
    proc = _run_cli("--root", str(tmp_path), "--json")
    assert proc.returncode == 1, proc.stdout + proc.stderr
    report = json.loads(proc.stdout)
    assert not report["clean"]
    rules = {(f["checker"], f["rule"]) for f in report["findings"]}
    assert ("index-dtype", "arange-dtype") in rules
    assert ("lock-discipline", "wal-under-broadcast-lock") in rules


def test_cli_single_checker_and_listing():
    proc = _run_cli("--list-checkers")
    assert proc.returncode == 0
    assert "lock-discipline" in proc.stdout
    proc = _run_cli("--checker", "thread-hygiene")
    assert proc.returncode == 0, proc.stdout + proc.stderr


class TestEvictionDisciplineFixtures:
    """controllers/ pod delete/evict sites must sit on a call-graph slice
    holding BOTH the rate-limiter grant and the idempotent intent record
    (ISSUE 16: a naked eviction is unthrottled under zone disruption and
    replayable after a controller restart)."""

    def test_flags_naked_delete(self):
        bad = textwrap.dedent("""
            class Reaper:
                def drain(self, node):
                    for pod in self.cs.pods():
                        if pod.node_name == node:
                            self.cs.delete_pod(pod)
        """)
        fs = check_source(checker_by_id("eviction-discipline"), bad)
        assert _rules(fs) == ["eviction-outside-funnel"]
        assert len(fs) == 1

    def test_flags_limiter_without_intent(self):
        """A throttle with no ledger rate-limits the double-evictions —
        it does not prevent them. Still a finding."""
        bad = textwrap.dedent("""
            class Reaper:
                def drain(self, zone, pod):
                    if self._buckets[zone].try_take():
                        self.cs.evict_pod(pod.uid, pod.node_name, "x")
        """)
        fs = check_source(checker_by_id("eviction-discipline"), bad)
        assert _rules(fs) == ["eviction-outside-funnel"]

    def test_flags_intent_without_limiter(self):
        bad = textwrap.dedent("""
            class Reaper:
                def drain(self, pod):
                    intent = intent_for(pod.uid, pod.node_name)
                    self.cs.evict_pod(pod.uid, pod.node_name, intent)
        """)
        fs = check_source(checker_by_id("eviction-discipline"), bad)
        assert _rules(fs) == ["eviction-outside-funnel"]

    def test_passes_full_funnel_in_one_def(self):
        good = textwrap.dedent("""
            class Evictor:
                def drain(self, zone, pod):
                    if not self._buckets[zone].try_take():
                        return
                    intent = intent_for(pod.uid, pod.node_name)
                    self.cs.evict_pod(pod.uid, pod.node_name, intent)
        """)
        assert check_source(checker_by_id("eviction-discipline"), good) == []

    def test_passes_run_once_shape(self):
        """The real evictor's shape: the token is taken one frame above
        the intent stamp — the caller's slice covers the call site."""
        good = textwrap.dedent("""
            class Evictor:
                def run_once(self):
                    for zone, q in self._queues.items():
                        while q and self._buckets[zone].try_take():
                            self._evict_one(q.popleft())
                def _evict_one(self, item):
                    intent = intent_for(item.uid, item.node)
                    self.cs.evict_pod(item.uid, item.node, intent)
        """)
        assert check_source(checker_by_id("eviction-discipline"), good) == []

    def test_scope_is_controllers_only(self):
        ck = checker_by_id("eviction-discipline")
        assert ck.applies_to("kubernetes_tpu/controllers/evictor.py")
        assert ck.applies_to("controllers/node_lifecycle.py")
        assert not ck.applies_to("kubernetes_tpu/core/scheduler.py")
        assert not ck.applies_to("tests/test_node_lifecycle.py")

    def test_real_evictor_module_is_clean(self):
        import kubernetes_tpu.controllers.evictor as ev
        import inspect
        src = inspect.getsource(ev)
        assert check_source(checker_by_id("eviction-discipline"), src,
                            "kubernetes_tpu/controllers/evictor.py") == []

    def test_lock_discipline_scope_covers_controllers(self):
        """Satellite: the lock-discipline scan now walks controllers/ too —
        a sleep under a held lock in a controller module must flag."""
        ck = checker_by_id("lock-discipline")
        assert ck.applies_to("kubernetes_tpu/controllers/node_lifecycle.py")


class TestDescheduleDisciplineFixtures:
    """Descheduler modules under controllers/ may only emit evictions on
    a call-graph slice holding BOTH the scored-improvement gate and the
    deterministic intent record (ISSUE 20: an ungated move is churn —
    ping-pong between near-balanced nodes — and an unintended one is
    unreplayable across a standby takeover)."""

    def test_flags_ungated_unintended_move(self):
        bad = textwrap.dedent("""
            class Descheduler:
                def rebalance(self, plan):
                    for pod, node in plan:
                        self.evictor.enqueue("z", node, pod.uid)
        """)
        fs = check_source(checker_by_id("deschedule-discipline"), bad)
        assert _rules(fs) == ["move-without-scored-gate"]
        assert len(fs) == 1

    def test_flags_gate_without_intent(self):
        """Scored but anonymous: the takeover's re-derived wave cannot
        replay into the ledger. Still a finding."""
        bad = textwrap.dedent("""
            class Descheduler:
                def rebalance(self, moves, floor):
                    for mv in moves:
                        if clears_hysteresis(mv.improvement, floor):
                            self.evictor.enqueue("z", mv.node, mv.uid)
        """)
        fs = check_source(checker_by_id("deschedule-discipline"), bad)
        assert _rules(fs) == ["move-without-scored-gate"]

    def test_flags_intent_without_gate(self):
        bad = textwrap.dedent("""
            class Descheduler:
                def rebalance(self, moves):
                    for mv in moves:
                        intent = intent_for(mv.uid, mv.node)
                        self.cs.evict_pod(mv.uid, mv.node, intent)
        """)
        fs = check_source(checker_by_id("deschedule-discipline"), bad)
        assert _rules(fs) == ["move-without-scored-gate"]

    def test_passes_reconcile_emit_shape(self):
        """The real controller's shape: the gate runs in reconcile_once,
        the intent is minted one frame below in _emit — the caller's
        closure holds both sinks plus the emit site."""
        good = textwrap.dedent("""
            class Descheduler:
                def reconcile_once(self, cands, floor):
                    for c in cands:
                        if clears_hysteresis(c.improvement, floor):
                            self._emit(c)
                def _emit(self, c):
                    intent = intent_for(c.uid, c.node)
                    self.planned[c.uid] = intent
                    self.evictor.enqueue(c.zone, c.node, c.uid)
        """)
        assert check_source(checker_by_id("deschedule-discipline"),
                            good) == []

    def test_scope_is_descheduler_modules_only(self):
        """Composes with eviction-discipline: that one covers ALL of
        controllers/; this one only bites descheduler modules (the
        node-lifecycle evictor legitimately moves pods ungated — its
        seats are ILLEGAL, there is no score to clear)."""
        ck = checker_by_id("deschedule-discipline")
        assert ck.applies_to("kubernetes_tpu/controllers/descheduler.py")
        assert ck.applies_to("controllers/descheduler.py")
        assert not ck.applies_to(
            "kubernetes_tpu/controllers/node_lifecycle.py")
        assert not ck.applies_to("kubernetes_tpu/ops/whatif.py")
        assert not ck.applies_to("tests/test_descheduler.py")

    def test_real_descheduler_module_is_clean(self):
        import kubernetes_tpu.controllers.descheduler as ds
        import inspect
        src = inspect.getsource(ds)
        assert check_source(
            checker_by_id("deschedule-discipline"), src,
            "kubernetes_tpu/controllers/descheduler.py") == []


class TestReconcileDisciplineFixtures:
    """controllers/ pod create sites must sit on a call-graph slice
    holding BOTH a deterministic-name source and a create-409-is-success
    handler (ISSUE 17: HA reconcilers racing a lease — or one reconciler
    across a kill9 — must collide benignly, never duplicate pods)."""

    def test_flags_random_named_create(self):
        bad = textwrap.dedent("""
            import uuid
            class Reconciler:
                def heal(self, rs):
                    for _ in range(rs.missing):
                        self.cs.create_pod(self.pod(uuid.uuid4().hex))
        """)
        fs = check_source(checker_by_id("reconcile-discipline"), bad)
        assert _rules(fs) == ["create-outside-seam"]
        assert len(fs) == 1

    def test_flags_deterministic_name_without_409_seam(self):
        """Deterministic names alone still crash the CAS loser: the
        second actor's create raises 409 and the reconciler error-loops.
        Still a finding."""
        bad = textwrap.dedent("""
            class Reconciler:
                def heal(self, rs):
                    for i in range(rs.replicas):
                        name = replica_name(rs.name, rs.revision, i)
                        self.cs.create_pod(self.pod(name))
        """)
        fs = check_source(checker_by_id("reconcile-discipline"), bad)
        assert _rules(fs) == ["create-outside-seam"]

    def test_flags_409_seam_without_deterministic_name(self):
        """409-tolerance over random names never fires — the duplicates
        don't collide, they coexist. Still a finding."""
        bad = textwrap.dedent("""
            import uuid
            class Reconciler:
                def heal(self, rs):
                    try:
                        self.cs.create_pod(self.pod(uuid.uuid4().hex))
                    except HTTPError as e:
                        if e.code != 409:
                            raise
        """)
        fs = check_source(checker_by_id("reconcile-discipline"), bad)
        assert _rules(fs) == ["create-outside-seam"]

    def test_passes_full_seam_in_one_def(self):
        good = textwrap.dedent("""
            class Reconciler:
                def heal(self, rs, i):
                    name = replica_name(rs.name, rs.revision, i)
                    try:
                        self.cs.create_pod(self.pod(name))
                    except HTTPError as e:
                        if e.code != 409:
                            raise
        """)
        assert check_source(
            checker_by_id("reconcile-discipline"), good) == []

    def test_passes_mint_seam_shape(self):
        """The real controllers' shape: the name is derived one frame
        above the create seam — the caller's slice covers the site."""
        good = textwrap.dedent("""
            def _create_pod(cs, pod):
                try:
                    cs.create_pod(pod)
                    return True
                except HTTPError as e:
                    if e.code == 409:
                        return False
                    raise
            class Reconciler:
                def heal(self, rs):
                    for i in range(rs.replicas):
                        name = replica_name(rs.name, rs.revision, i)
                        _create_pod(self.cs, self.pod(name))
        """)
        assert check_source(
            checker_by_id("reconcile-discipline"), good) == []

    def test_scope_is_controllers_only(self):
        ck = checker_by_id("reconcile-discipline")
        assert ck.applies_to("kubernetes_tpu/controllers/workload.py")
        assert ck.applies_to("controllers/autoscaler.py")
        assert not ck.applies_to("kubernetes_tpu/core/scheduler.py")
        assert not ck.applies_to("tests/test_node_lifecycle.py")

    def test_real_workload_module_is_clean(self):
        import inspect

        import kubernetes_tpu.controllers.workload as wk
        src = inspect.getsource(wk)
        assert check_source(checker_by_id("reconcile-discipline"), src,
                            "kubernetes_tpu/controllers/workload.py") == []


def test_cli_seeded_racy_create_exits_nonzero(tmp_path):
    """Acceptance (ISSUE 17): `reconcile-discipline` exits 1 on a seeded
    racy-create fixture under controllers/."""
    ctl = tmp_path / "controllers"
    ctl.mkdir()
    (ctl / "healer.py").write_text(
        "import uuid\n"
        "class Healer:\n"
        "    def heal(self, rs):\n"
        "        self.cs.create_pod(self.pod(uuid.uuid4().hex))\n")
    proc = _run_cli("--root", str(tmp_path), "--checker",
                    "reconcile-discipline", "--json")
    assert proc.returncode == 1, proc.stdout + proc.stderr
    report = json.loads(proc.stdout)
    rules = {(f["checker"], f["rule"]) for f in report["findings"]}
    assert ("reconcile-discipline", "create-outside-seam") in rules


def test_cli_seeded_naked_delete_exits_nonzero(tmp_path):
    """Acceptance (ISSUE 16): `eviction-discipline` exits 1 on a seeded
    naked-delete fixture under controllers/."""
    ctl = tmp_path / "controllers"
    ctl.mkdir()
    (ctl / "reaper.py").write_text(
        "class Reaper:\n"
        "    def drain(self, node):\n"
        "        for pod in self.cs.pods():\n"
        "            self.cs.delete_pod(pod)\n")
    proc = _run_cli("--root", str(tmp_path), "--checker",
                    "eviction-discipline", "--json")
    assert proc.returncode == 1, proc.stdout + proc.stderr
    report = json.loads(proc.stdout)
    rules = {(f["checker"], f["rule"]) for f in report["findings"]}
    assert ("eviction-discipline", "eviction-outside-funnel") in rules


def test_cli_seeded_ungated_move_exits_nonzero(tmp_path):
    """Acceptance (ISSUE 20): `deschedule-discipline` exits 1 on a seeded
    ungated-move fixture under controllers/."""
    ctl = tmp_path / "controllers"
    ctl.mkdir()
    (ctl / "descheduler.py").write_text(
        "class Descheduler:\n"
        "    def rebalance(self, plan):\n"
        "        for pod, node in plan:\n"
        "            self.evictor.enqueue('z', node, pod.uid)\n")
    proc = _run_cli("--root", str(tmp_path), "--checker",
                    "deschedule-discipline", "--json")
    assert proc.returncode == 1, proc.stdout + proc.stderr
    report = json.loads(proc.stdout)
    rules = {(f["checker"], f["rule"]) for f in report["findings"]}
    assert ("deschedule-discipline", "move-without-scored-gate") in rules


class TestSupervisionDisciplineFixtures:
    """fleet/ child spawn sites must sit on a call-graph slice holding
    BOTH a readiness barrier and drain_pipe wiring (ISSUE 19: a spawn
    without the barrier races the staged bring-up; without the drain, a
    chatty child wedges on a full 64KB pipe — the PR-8 stall class)."""

    def test_flags_naked_popen_both_rules(self):
        bad = textwrap.dedent("""
            import subprocess

            class Conductor:
                def launch(self, cmd):
                    return subprocess.Popen(cmd)
        """)
        fs = check_source(checker_by_id("supervision-discipline"), bad,
                          "kubernetes_tpu/fleet/conductor.py")
        rules = {f.rule for f in fs}
        assert rules == {"spawn-no-barrier", "spawn-no-drain"}

    def test_flags_spawn_ready_without_drain(self):
        """spawn_ready IS the readiness barrier (it blocks on the child's
        first ready line) — but the drain still has to be wired."""
        bad = textwrap.dedent("""
            from ..testing.faults import spawn_ready

            class Conductor:
                def launch(self, member):
                    member.proc = spawn_ready(member.cmd, member.pattern)
        """)
        fs = check_source(checker_by_id("supervision-discipline"), bad,
                          "kubernetes_tpu/fleet/conductor.py")
        assert {f.rule for f in fs} == {"spawn-no-drain"}

    def test_passes_full_discipline_in_one_def(self):
        good = textwrap.dedent("""
            from ..testing.faults import drain_pipe, spawn_ready

            class Conductor:
                def launch(self, member):
                    member.proc = spawn_ready(member.cmd, member.pattern)
                    member.tail = drain_pipe(member.proc)
        """)
        assert check_source(checker_by_id("supervision-discipline"), good,
                            "kubernetes_tpu/fleet/conductor.py") == []

    def test_passes_barrier_one_frame_above_the_spawn(self):
        """The start → _start_shards → _spawn shape: a raw Popen in a
        helper is covered when a caller's slice holds the lease barrier
        and the drain wiring."""
        good = textwrap.dedent("""
            import subprocess

            class Conductor:
                def _spawn(self, cmd):
                    proc = subprocess.Popen(cmd)
                    self._tails.append(drain_pipe(proc))
                    return proc

                def start_shards(self):
                    for cmd in self.cmds:
                        self._spawn(cmd)
                    self._wait_shards_leased()

                def _wait_shards_leased(self):
                    pass
        """)
        assert check_source(checker_by_id("supervision-discipline"), good,
                            "kubernetes_tpu/fleet/conductor.py") == []

    def test_scope_is_fleet_only(self):
        ck = checker_by_id("supervision-discipline")
        assert ck.applies_to("kubernetes_tpu/fleet/conductor.py")
        assert ck.applies_to("fleet/__main__.py")
        assert not ck.applies_to("kubernetes_tpu/shard/harness.py")
        assert not ck.applies_to("kubernetes_tpu/testing/faults.py")
        assert not ck.applies_to("tests/test_fleet.py")

    def test_real_conductor_module_is_clean(self):
        import inspect

        import kubernetes_tpu.fleet.conductor as cond
        src = inspect.getsource(cond)
        assert check_source(checker_by_id("supervision-discipline"), src,
                            "kubernetes_tpu/fleet/conductor.py") == []

    def test_lock_discipline_scope_covers_fleet(self):
        """Satellite: the lock-discipline scan walks fleet/ too — a sleep
        under a held lock in the conductor must flag."""
        ck = checker_by_id("lock-discipline")
        assert ck.applies_to("kubernetes_tpu/fleet/conductor.py")
