"""Per-rule pbox-lint coverage: each rule fires on a violation, stays quiet
on clean code, and honors inline suppressions; plus baseline round-trip and
the CLI exit-code contract (docs/STATIC_ANALYSIS.md)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from paddlebox_tpu.analysis import (
    ERROR,
    WARNING,
    apply_baseline,
    default_rules,
    lint_paths,
    load_baseline,
    save_baseline,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lint_source(tmp_path, source, name="mod.py", extra_files=()):
    """Write ``source`` (and any (name, src) extras) under tmp_path, lint."""
    p = tmp_path / name
    p.write_text(textwrap.dedent(source))
    paths = [str(p)]
    for fname, src in extra_files:
        q = tmp_path / fname
        q.parent.mkdir(parents=True, exist_ok=True)
        q.write_text(textwrap.dedent(src))
        paths.append(str(q))
    return lint_paths(paths, default_rules(), root=str(tmp_path))


def rule_findings(result, rule):
    return [f for f in result.findings if f.rule == rule]


# ---- JIT001 ----------------------------------------------------------------


class TestJitPurity:
    def test_positive(self, tmp_path):
        res = lint_source(tmp_path, """
            import time
            import jax
            import numpy as np

            @jax.jit
            def step(x):
                t = time.time()
                y = x.item()
                z = float(x) + int(x)
                w = np.asarray(x)
                if x > 0:
                    y = 1.0
                return y
        """)
        msgs = [f.message for f in rule_findings(res, "JIT001")]
        assert any("host clock" in m for m in msgs)
        assert any(".item()" in m for m in msgs)
        assert any("float()" in m for m in msgs)
        assert any("np.asarray()" in m for m in msgs)
        assert any("Python `if`" in m for m in msgs)

    def test_call_form_and_partial(self, tmp_path):
        # jitted by reference (jax.jit(step)) and via functools.partial
        res = lint_source(tmp_path, """
            import functools
            import jax

            def step(x):
                return x.item()

            fast = jax.jit(step)

            @functools.partial(jax.jit, static_argnames=("mode",))
            def go(x, mode):
                if mode:          # static arg: fine
                    return x
                return float(x)   # traced arg: flagged
        """)
        msgs = [f.message for f in rule_findings(res, "JIT001")]
        assert any(".item()" in m for m in msgs)
        assert any("float()" in m for m in msgs)
        assert not any("Python `if`" in m for m in msgs)

    def test_clean(self, tmp_path):
        # shape reads, is-None checks, jnp use: all trace-static
        res = lint_source(tmp_path, """
            import jax
            import jax.numpy as jnp

            @jax.jit
            def step(x, mask):
                if x.ndim != 2:
                    raise ValueError(x.shape)
                if mask is None:
                    mask = jnp.ones(x.shape[0])
                return jnp.where(mask > 0, x.sum(axis=1), 0.0)

            def host_side(arr):
                return float(arr.sum())  # not jitted: fine
        """)
        assert rule_findings(res, "JIT001") == []

    def test_suppressed(self, tmp_path):
        res = lint_source(tmp_path, """
            import jax

            @jax.jit
            def step(x):
                return x.item()  # pbox-lint: disable=JIT001
        """)
        assert rule_findings(res, "JIT001") == []


# ---- THR002 ----------------------------------------------------------------


class TestLockDiscipline:
    def test_thread_reachable_is_error(self, tmp_path):
        res = lint_source(tmp_path, """
            import threading

            class Box:
                def __init__(self):
                    self._data = []  # guarded-by: _lock
                    self._lock = threading.Lock()
                    threading.Thread(target=self._loop).start()

                def _loop(self):
                    self._data.append(1)
        """)
        errs = [f for f in rule_findings(res, "THR002") if f.severity == ERROR]
        assert len(errs) == 1
        assert "thread entry point" in errs[0].message

    def test_unreachable_is_warning_and_locked_is_clean(self, tmp_path):
        res = lint_source(tmp_path, """
            import threading

            class Box:
                def __init__(self):
                    self._data = []  # guarded-by: _lock
                    self._lock = threading.Lock()

                def locked(self):
                    with self._lock:
                        return len(self._data)

                def bare(self):
                    return self._data
        """)
        found = rule_findings(res, "THR002")
        assert len(found) == 1
        assert found[0].severity == WARNING
        assert "Box.bare" in found[0].message

    def test_module_global_and_submit_entry(self, tmp_path):
        res = lint_source(tmp_path, """
            import threading
            from concurrent.futures import ThreadPoolExecutor

            _lock = threading.Lock()
            _count = 0  # guarded-by: _lock

            def worker():
                global _count
                _count += 1

            def launch(ex: ThreadPoolExecutor):
                ex.submit(worker)

            def safe():
                with _lock:
                    return _count
        """)
        errs = [f for f in rule_findings(res, "THR002") if f.severity == ERROR]
        assert len(errs) == 1
        assert "worker" in errs[0].message

    def test_suppressed(self, tmp_path):
        res = lint_source(tmp_path, """
            import threading

            class Box:
                def __init__(self):
                    self._data = []  # guarded-by: _lock
                    self._lock = threading.Lock()

                def bare(self):
                    return self._data  # pbox-lint: disable=THR002
        """)
        assert rule_findings(res, "THR002") == []


# ---- REG003 ----------------------------------------------------------------

FAULTINJECT_STUB = """
    KNOWN_SITES = ("good.site",)

    def fire(site):
        pass
"""


class TestRegistryConsistency:
    def test_undefined_read_and_dead_define(self, tmp_path):
        res = lint_source(tmp_path, """
            from paddlebox_tpu import config

            config.define_flag("lonely_knob", 1, "never read")

            def use():
                return config.get_flag("phantom_knob")
        """)
        errs = [f for f in rule_findings(res, "REG003") if f.severity == ERROR]
        warns = [f for f in rule_findings(res, "REG003") if f.severity == WARNING]
        assert len(errs) == 1 and "phantom_knob" in errs[0].message
        assert len(warns) == 1 and "lonely_knob" in warns[0].message

    def test_unknown_fault_site(self, tmp_path):
        res = lint_source(
            tmp_path,
            """
            from paddlebox_tpu.utils.faultinject import fire

            def f():
                fire("good.site")
                fire("typo.site")
            """,
            extra_files=[("utils/faultinject.py", FAULTINJECT_STUB)],
        )
        errs = [f for f in rule_findings(res, "REG003") if f.severity == ERROR]
        assert len(errs) == 1
        assert "typo.site" in errs[0].message

    def test_clean_and_dynamic_names_skipped(self, tmp_path):
        res = lint_source(tmp_path, """
            from paddlebox_tpu import config

            config.define_flag("real_knob", 2, "read below")

            def use(name):
                config.get_flag(name)  # dynamic: not checkable
                return config.get_flag("real_knob")
        """)
        assert rule_findings(res, "REG003") == []

    def test_suppressed(self, tmp_path):
        res = lint_source(tmp_path, """
            from paddlebox_tpu import config

            def use():
                return config.get_flag("phantom")  # pbox-lint: disable=REG003
        """)
        assert rule_findings(res, "REG003") == []


# ---- IO004 -----------------------------------------------------------------


class TestDurableWrite:
    def test_positive_all_write_modes(self, tmp_path):
        res = lint_source(tmp_path, """
            def bad(p):
                open(p, "w").write("x")
                open(p, "wb").write(b"x")
                open(p, "a").write("x")
                open(p, mode="r+").write("x")
        """)
        assert len(rule_findings(res, "IO004")) == 4

    def test_clean(self, tmp_path):
        res = lint_source(tmp_path, """
            def good(p, m):
                open(p).read()
                open(p, "rb").read()
                open(p, m).read()  # non-literal mode: skipped
        """)
        assert rule_findings(res, "IO004") == []

    def test_suppressed(self, tmp_path):
        res = lint_source(tmp_path, """
            def wrapper(p):
                return open(p, "w")  # pbox-lint: disable=IO004
        """)
        assert rule_findings(res, "IO004") == []


# ---- MON005 ----------------------------------------------------------------


class TestStatNames:
    def test_positive(self, tmp_path):
        res = lint_source(tmp_path, """
            from paddlebox_tpu.utils.monitor import STAT_ADD, STAT_SET

            def f(kind):
                STAT_ADD("Bad-Name")
                STAT_SET(f"dyn_{kind}", 1)
        """)
        msgs = [f.message for f in rule_findings(res, "MON005")]
        assert len(msgs) == 2
        assert any("Bad-Name" in m for m in msgs)
        assert any("string literal" in m for m in msgs)

    def test_clean(self, tmp_path):
        res = lint_source(tmp_path, """
            from paddlebox_tpu.utils.monitor import STAT_ADD, STAT_GET

            def f(name):
                STAT_ADD("pass.auc_updates", 2)
                STAT_GET(name)  # reads may be programmatic
        """)
        assert rule_findings(res, "MON005") == []

    def test_suppressed(self, tmp_path):
        res = lint_source(tmp_path, """
            from paddlebox_tpu.utils.monitor import STAT_ADD

            def f(kind):
                STAT_ADD(f"sup_{kind}")  # pbox-lint: disable=MON005
        """)
        assert rule_findings(res, "MON005") == []

    def test_observe_covered(self, tmp_path):
        # STAT_OBSERVE mints histogram names into the same enumerable
        # namespace as the counters — same literal discipline
        res = lint_source(tmp_path, """
            from paddlebox_tpu.utils.monitor import STAT_OBSERVE

            def f(name, v):
                STAT_OBSERVE("serve.latency_ms", v)  # ok
                STAT_OBSERVE("serve.request_ms", v)  # ok (the SLO series)
                STAT_OBSERVE("Bad-Hist", v)
                STAT_OBSERVE(name, v)
        """)
        msgs = [f.message for f in rule_findings(res, "MON005")]
        assert len(msgs) == 2
        assert any("Bad-Hist" in m for m in msgs)
        assert any("string literal" in m for m in msgs)


# ---- THR006 ----------------------------------------------------------------


class TestRaceDetector:
    def test_positive(self, tmp_path):
        # _push is reachable from BOTH the spawned thread (via _worker)
        # and the main thread (via the uncalled root `add`) with no lock
        res = lint_source(tmp_path, """
            import threading

            class Box:
                def __init__(self):
                    self.items = []
                    threading.Thread(target=self._worker).start()

                def _push(self):
                    self.items.append(1)

                def _worker(self):
                    self._push()

                def add(self):
                    self._push()
        """)
        errs = rule_findings(res, "THR006")
        assert errs, "two-thread unlocked mutation must fire"
        assert any("items" in f.message for f in errs)

    def test_locked_on_both_sides_is_quiet(self, tmp_path):
        res = lint_source(tmp_path, """
            import threading

            class Box:
                def __init__(self):
                    self.items = []
                    self._lock = threading.Lock()
                    threading.Thread(target=self._worker).start()

                def _worker(self):
                    with self._lock:
                        self.items.append(1)

                def add(self, x):
                    with self._lock:
                        self.items.append(x)
        """)
        assert rule_findings(res, "THR006") == []

    def test_lock_held_on_call_path_is_quiet(self, tmp_path):
        # the callee never takes the lock itself — every caller does; the
        # meet-over-paths propagation must see it as protected
        res = lint_source(tmp_path, """
            import threading

            class Box:
                def __init__(self):
                    self.items = []
                    self._lock = threading.Lock()
                    threading.Thread(target=self._worker).start()

                def _grow(self):
                    self.items.append(0)

                def _worker(self):
                    with self._lock:
                        self._grow()

                def add(self):
                    with self._lock:
                        self._grow()
        """)
        assert rule_findings(res, "THR006") == []

    def test_single_thread_is_quiet(self, tmp_path):
        res = lint_source(tmp_path, """
            class Box:
                def __init__(self):
                    self.items = []

                def add(self, x):
                    self.items.append(x)
        """)
        assert rule_findings(res, "THR006") == []

    def test_a_call_on_an_imported_module_is_no_method_call(self, tmp_path):
        # ``json.load`` in a worker must not link the thread to the one class
        # of the scanned set that has a method ``load``
        res = lint_source(tmp_path, """
            import json
            import threading

            class Store:
                def __init__(self):
                    self.epoch = 0
                    threading.Thread(target=read_meta, args=("m",)).start()

                def load(self, path):
                    self.epoch = 1

            def read_meta(path):
                with open(path) as f:
                    return json.load(f)

            def restore(store, path):
                store.load(path)
        """)
        assert rule_findings(res, "THR006") == []

    def test_synchronized_by_annotation_is_quiet(self, tmp_path):
        # same two-thread _stage shape as the positive, but the init site
        # documents the non-lock mechanism — the annotation exempts it
        res = lint_source(tmp_path, """
            import threading

            class Box:
                def __init__(self):
                    self.staged = None  # synchronized-by: worker join handoff
                    self._t = threading.Thread(target=self._worker)
                    self._t.start()

                def _stage(self, v):
                    self.staged = v

                def _worker(self):
                    self._stage([1])

                def consume(self):
                    self._t.join()
                    self._stage(None)
        """)
        assert rule_findings(res, "THR006") == []

    def test_suppressed(self, tmp_path):
        res = lint_source(tmp_path, """
            import threading

            class Box:
                def __init__(self):
                    self.items = []
                    threading.Thread(target=self._worker).start()

                def _push(self):
                    self.items.append(1)  # pbox-lint: disable=THR006

                def _worker(self):
                    self._push()

                def add(self):
                    self._push()
        """)
        assert rule_findings(res, "THR006") == []


# ---- EXC007 ----------------------------------------------------------------


class TestExceptionFlow:
    def test_positive_silent_swallow(self, tmp_path):
        res = lint_source(tmp_path, """
            def f():
                try:
                    return 1
                except Exception:
                    pass
        """)
        errs = rule_findings(res, "EXC007")
        assert len(errs) == 1
        assert "silently swallows" in errs[0].message

    def test_counted_or_recorded_swallow_is_quiet(self, tmp_path):
        res = lint_source(tmp_path, """
            from paddlebox_tpu.utils.monitor import STAT_ADD

            def counted():
                try:
                    return 1
                except OSError:
                    STAT_ADD("x.oserrors")

            def logged(log):
                try:
                    return 1
                except Exception as e:
                    log.warning("boom %r", e)
        """)
        assert rule_findings(res, "EXC007") == []

    def test_deferred_surface_is_quiet(self, tmp_path):
        # storing or handing off the bound exception is a deferred
        # re-raise, not a swallow
        res = lint_source(tmp_path, """
            def stored(self):
                try:
                    return 1
                except Exception as e:
                    self._exc = e

            def handed(errors):
                try:
                    return 1
                except BaseException as e:
                    errors.append(e)
        """)
        assert rule_findings(res, "EXC007") == []

    def test_narrow_handler_is_quiet(self, tmp_path):
        res = lint_source(tmp_path, """
            def f():
                try:
                    return 1
                except (KeyError, ValueError):
                    return None
        """)
        assert rule_findings(res, "EXC007") == []

    def test_suppressed_next_line_directive(self, tmp_path):
        res = lint_source(tmp_path, """
            def f():
                try:
                    return 1
                # absence probe: None IS the answer
                # pbox-lint: disable=EXC007
                except OSError:
                    return None
        """)
        assert rule_findings(res, "EXC007") == []


# ---- FLT008 ----------------------------------------------------------------

FAULT_CATALOG_STUB = """
    KNOWN_SITES = (
        "covered.site",
        "dead.site",
        "untested.site",
    )

    def fire(site):
        pass
"""


class TestFaultSiteCoverage:
    def fixture(self, tmp_path, test_src):
        return lint_source(
            tmp_path,
            """
            from paddlebox_tpu.utils.faultinject import fire

            def a():
                fire("covered.site")

            def b():
                fire("untested.site")
            """,
            name="pkg_mod.py",
            extra_files=[
                ("utils/faultinject.py", FAULT_CATALOG_STUB),
                ("tests/test_cov.py", test_src),
            ],
        )

    def test_dead_and_untested_sites_fire(self, tmp_path):
        res = self.fixture(tmp_path, """
            def test_covered():
                assert "covered.site"
        """)
        msgs = [f.message for f in rule_findings(res, "FLT008")]
        # dead.site draws both findings (never fired AND never referenced)
        assert len(msgs) == 3
        assert any("dead.site" in m and "never fired" in m for m in msgs)
        assert any(
            "untested.site" in m and "not referenced" in m for m in msgs
        )
        assert not any("covered.site" in m for m in msgs)

    def test_full_coverage_is_quiet(self, tmp_path):
        res = self.fixture(tmp_path, """
            SCHEDULE = ["covered.site", "untested.site", "dead.site"]
        """)
        msgs = [f.message for f in rule_findings(res, "FLT008")]
        # dead.site is still never FIRED by package code
        assert len(msgs) == 1 and "dead.site" in msgs[0]


# ---- DST009 ----------------------------------------------------------------


class TestDistributedDiscipline:
    def test_black_holed_send(self, tmp_path):
        res = lint_source(tmp_path, """
            def push(tp):
                tp.send(1, "ctl:orphan:ping", b"")

            def paired(tp):
                tp.send(1, "ctl:pair:pong", b"")

            def pull(tp):
                return tp.recv("ctl:pair:pong", 0)
        """)
        msgs = [f.message for f in rule_findings(res, "DST009")]
        assert len(msgs) == 1
        assert "ctl:orphan:ping" in msgs[0] and "black-holed" in msgs[0]

    def test_rank_conditional_collective(self, tmp_path):
        res = lint_source(tmp_path, """
            def lopsided(tp):
                if tp.rank == 0:
                    tp.allgather(b"", "ctl:member:probe")

            def symmetric(tp):
                if tp.rank == 0:
                    tp.allgather(b"lead", "barrier:x")
                else:
                    tp.allgather(b"flw", "barrier:x")

            def pull(tp):
                # the lopsided member tag still needs a nominal receiver
                return tp.recv("ctl:member:probe", 0)
        """)
        msgs = [f.message for f in rule_findings(res, "DST009")]
        assert len(msgs) == 1
        assert "static deadlock" in msgs[0] and "allgather" in msgs[0]

    def test_verdict_discipline(self, tmp_path):
        res = lint_source(tmp_path, """
            class Sup:
                def exchange_verdict(self, key, ok, detail="", fatal=False):
                    return ok

                def unfenced(self, tp):
                    tp.allgather(b"", "ctl:verdict:load")

                def unfingerprinted(self, ok):
                    self.exchange_verdict("migrate", ok, fatal=True)

                def fenced_commit(self, ok, m):
                    key = "migrate:" + m.fingerprint()
                    self.exchange_verdict(key, ok, fatal=True)
        """)
        msgs = [f.message for f in rule_findings(res, "DST009")]
        assert any("no @e epoch" in m and "split-brain" in m for m in msgs)
        assert any("fingerprint()" in m and "fatal=True" in m for m in msgs)
        assert len(msgs) == 2  # fenced_commit stays quiet

    def test_clean_protocol_is_quiet(self, tmp_path):
        res = lint_source(tmp_path, """
            def exchange(tp, epoch):
                tp.send(1, f"ctl:state:{tp.rank}@e{epoch}", b"")
                got = tp.recv(f"ctl:state:{1 - tp.rank}@e{epoch}", 1 - tp.rank)
                tp.allgather(got, f"ctl:round:sync@e{epoch}")
        """)
        assert rule_findings(res, "DST009") == []

    def test_suppressed(self, tmp_path):
        res = lint_source(tmp_path, """
            def push(tp):
                # best-effort diagnostic frame; loss is acceptable
                # pbox-lint: disable=DST009
                tp.send(1, "ctl:orphan:ping", b"")
        """)
        assert rule_findings(res, "DST009") == []


# ---- RES010 ----------------------------------------------------------------


class TestResourceLifecycle:
    def test_thread_positive(self, tmp_path):
        res = lint_source(tmp_path, """
            import threading

            def fire_and_forget(fn):
                threading.Thread(target=fn).start()

            def bound_but_abandoned(fn):
                t = threading.Thread(target=fn)
                t.start()
                return t
        """)
        msgs = [f.message for f in rule_findings(res, "RES010")]
        assert any("never joinable" in m for m in msgs)
        assert any('"t" is never joined' in m for m in msgs)

    def test_thread_clean(self, tmp_path):
        res = lint_source(tmp_path, """
            import threading

            class Box:
                def spawn(self, fn):
                    self._th = threading.Thread(target=fn, daemon=False)
                    self._th.start()
                    w = threading.Thread(target=fn, daemon=True)
                    w.start()

                def stop(self):
                    t = getattr(self, "_th", None)
                    if t is not None:
                        t.join()
        """)
        assert rule_findings(res, "RES010") == []

    def test_socket_shutdown_before_close(self, tmp_path):
        res = lint_source(tmp_path, """
            import socket

            def bad_teardown(srv):
                conn, addr = srv.accept()
                conn.close()

            def good_teardown(srv):
                peer, addr = srv.accept()
                peer.shutdown(socket.SHUT_RDWR)
                peer.close()
        """)
        msgs = [f.message for f in rule_findings(res, "RES010")]
        assert len(msgs) == 1
        assert '"conn"' in msgs[0] and "shutdown()" in msgs[0]

    def test_listening_socket(self, tmp_path):
        res = lint_source(tmp_path, """
            import socket

            def serve_bad():
                s = socket.socket()
                s.listen(8)
                s.close()

            def port_pick_ok():
                # bind-only probe: no peer is ever blocked on it
                s2 = socket.socket()
                s2.bind(("127.0.0.1", 0))
                port = s2.getsockname()[1]
                s2.close()
                return port
        """)
        msgs = [f.message for f in rule_findings(res, "RES010")]
        assert len(msgs) == 1 and '"s"' in msgs[0]

    def test_executor_and_open(self, tmp_path):
        res = lint_source(tmp_path, """
            from concurrent.futures import ThreadPoolExecutor

            def leaky(fn, path):
                ex = ThreadPoolExecutor(2)
                ex.submit(fn)
                f = open(path)
                return f.read()

            def tidy(fn, path):
                with ThreadPoolExecutor(2) as ex:
                    ex.submit(fn)
                pool = ThreadPoolExecutor(2)
                pool.submit(fn)
                pool.shutdown(wait=True)
                with open(path) as f:
                    return f.read()
        """)
        msgs = [f.message for f in rule_findings(res, "RES010")]
        assert any('"ex"' in m and "shutdown()" in m for m in msgs)
        assert any('"f"' in m and "close()" in m for m in msgs)
        assert len(msgs) == 2

    def test_suppressed(self, tmp_path):
        res = lint_source(tmp_path, """
            import threading

            def watchdog(fn):
                # process-lifetime watcher; joined by interpreter exit
                # pbox-lint: disable=RES010
                threading.Thread(target=fn).start()
        """)
        assert rule_findings(res, "RES010") == []


# ---- baseline round-trip ---------------------------------------------------


class TestBaseline:
    def test_add_then_remove_round_trip(self, tmp_path):
        src = """
            def bad(p):
                open(p, "w").write("x")
        """
        res = lint_source(tmp_path, src)
        assert len(res.errors) == 1

        bl_path = str(tmp_path / "baseline.json")
        save_baseline(bl_path, res.findings)
        bl = load_baseline(bl_path)
        assert len(bl) == 1

        # grandfathered: same finding no longer gates
        new, old, stale = apply_baseline(res.findings, bl)
        assert [f for f in new if f.severity == ERROR] == []
        assert len(old) == 1 and stale == []

        # a SECOND identical violation exceeds the budget and gates
        res2 = lint_source(
            tmp_path,
            """
            def bad(p):
                open(p, "w").write("x")
                open(p, "w").write("y")
            """,
        )
        new2, old2, _ = apply_baseline(res2.findings, bl)
        assert len([f for f in new2 if f.severity == ERROR]) == 1
        assert len(old2) == 1

        # violation fixed -> baseline entry reported stale
        res3 = lint_source(tmp_path, "def ok():\n    return 1\n")
        new3, old3, stale3 = apply_baseline(res3.findings, bl)
        assert new3 == [] and old3 == [] and len(stale3) == 1

    def test_warnings_never_consume_budget(self, tmp_path):
        res = lint_source(tmp_path, """
            from paddlebox_tpu import config

            config.define_flag("dead_knob", 1, "warned, not gated")
        """)
        assert res.errors == []
        save_baseline(str(tmp_path / "b.json"), res.findings)
        assert load_baseline(str(tmp_path / "b.json")) == {}


# ---- CLI contract ----------------------------------------------------------


def run_cli(*args):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "run_lint.py"), *args],
        capture_output=True, text=True, timeout=120,
    )


class TestCli:
    def test_exit_codes_and_json(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text('def f(p):\n    open(p, "w")\n')
        bl = str(tmp_path / "bl.json")

        r = run_cli(str(bad), "--baseline", bl)
        assert r.returncode == 1, r.stdout + r.stderr
        assert "IO004" in r.stdout

        r = run_cli(str(bad), "--baseline", bl, "--format=json")
        assert r.returncode == 1
        payload = json.loads(r.stdout)
        assert payload["ok"] is False
        assert payload["new_errors"][0]["rule"] == "IO004"

        # baseline the finding -> clean exit; then fix -> stale reported
        r = run_cli(str(bad), "--baseline", bl, "--update-baseline")
        assert r.returncode == 0
        r = run_cli(str(bad), "--baseline", bl)
        assert r.returncode == 0
        assert "baseline" in r.stdout

        bad.write_text("def f(p):\n    return p\n")
        r = run_cli(str(bad), "--baseline", bl, "--format=json")
        assert r.returncode == 0
        payload = json.loads(r.stdout)
        assert payload["ok"] is True
        assert len(payload["stale_baseline"]) == 1

        r = run_cli(str(tmp_path / "no_such_dir"))
        assert r.returncode == 2

    def test_syntax_error_gates(self, tmp_path):
        broken = tmp_path / "broken.py"
        broken.write_text("def f(:\n")
        r = run_cli(str(broken), "--no-baseline")
        assert r.returncode == 1
        assert "syntax error" in r.stdout
