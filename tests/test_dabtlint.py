"""dabtlint test suite: every checker on seeded fixture snippets (one
positive + one near-miss negative per code), suppression/baseline mechanics,
the CLI gate, and the runtime lock-order witness — including the contract
test that a deliberately introduced ABBA cycle is convicted by BOTH the
static DABT101 pass and the runtime witness.

No jax required: everything here is AST analysis and pure-Python threading.
"""

from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys
import textwrap
import threading
from concurrent.futures import Future
from pathlib import Path

import pytest

REPO_ROOT = Path(__file__).resolve().parents[1]
TOOLS = REPO_ROOT / "tools"
if str(TOOLS) not in sys.path:  # repo-root conftest adds it; belt for direct runs
    sys.path.insert(0, str(TOOLS))

from dabtlint import Baseline, BaselineError, run_analysis  # noqa: E402
from dabtlint.cli import analyze_paths  # noqa: E402
from dabtlint.suppress import apply_suppressions  # noqa: E402
from dabtlint.witness import (  # noqa: E402
    LockOrderWitness,
    WitnessedLock,
    install,
    uninstall,
)
import dabtlint.witness as witness_mod  # noqa: E402


# --------------------------------------------------------------------- helpers
def _project(tmp_path: Path, files: dict) -> Path:
    root = tmp_path / "proj"
    for rel, src in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return root


def _findings(tmp_path: Path, files: dict, code: str | None = None):
    out = run_analysis([str(_project(tmp_path, files))])
    if code is not None:
        out = [f for f in out if f.code == code]
    return out


ABBA_SRC = """
    import threading

    lock_a = threading.Lock()
    lock_b = threading.Lock()

    def ab():
        with lock_a:
            with lock_b:
                pass

    def ba():
        with lock_b:
            with lock_a:
                pass
"""


# --------------------------------------------------------------------- DABT101
def test_dabt101_direct_abba_cycle(tmp_path):
    found = _findings(tmp_path, {"locksmod.py": ABBA_SRC}, "DABT101")
    assert len(found) == 1
    f = found[0]
    assert f.module == "proj/locksmod.py"
    assert "lock_a" in f.detail and "lock_b" in f.detail
    assert "legs:" in f.detail


def test_dabt101_same_order_is_clean(tmp_path):
    src = """
        import threading

        lock_a = threading.Lock()
        lock_b = threading.Lock()

        def one():
            with lock_a:
                with lock_b:
                    pass

        def two():
            with lock_a:
                with lock_b:
                    pass
    """
    assert _findings(tmp_path, {"locksmod.py": src}, "DABT101") == []


def test_dabt101_cycle_through_calls(tmp_path):
    src = """
        import threading

        lock_a = threading.Lock()
        lock_b = threading.Lock()

        def takes_a():
            with lock_a:
                pass

        def takes_b():
            with lock_b:
                pass

        def f():
            with lock_a:
                takes_b()

        def g():
            with lock_b:
                takes_a()
    """
    found = _findings(tmp_path, {"calls.py": src}, "DABT101")
    assert len(found) == 1
    assert "call to takes_" in found[0].detail


def test_dabt101_cycle_through_done_callback(tmp_path):
    src = """
        import threading

        lock_a = threading.Lock()
        lock_b = threading.Lock()

        def on_done(f):
            with lock_b:
                pass

        def resolver(fut):
            fut.add_done_callback(on_done)
            with lock_a:
                fut.set_result(1)

        def reverse():
            with lock_b:
                with lock_a:
                    pass
    """
    found = _findings(tmp_path, {"cb.py": src}, "DABT101")
    assert len(found) == 1
    assert "done-callback on_done()" in found[0].detail


# --------------------------------------------------------------------- DABT102
FUT_SRC = """
    import threading

    class Box:
        def __init__(self):
            self._lock = threading.Lock()

        def bad(self, fut):
            with self._lock:
                fut.set_result(1)

        def good(self, fut):
            out = []
            with self._lock:
                out.append(fut)
            out[0].set_result(1)
"""


def test_dabt102_resolve_under_lock(tmp_path):
    found = _findings(tmp_path, {"futmod.py": FUT_SRC}, "DABT102")
    assert [f.symbol for f in found] == ["Box.bad"]
    assert "Box._lock" in found[0].detail


def test_dabt102_interprocedural_and_cancel_heuristic(tmp_path):
    src = """
        import threading

        def helper(f):
            f.set_exception(RuntimeError("x"))

        class Box:
            def __init__(self):
                self._lock = threading.Lock()

            def via_helper(self, fut):
                with self._lock:
                    helper(fut)

            def cancels_future(self, fut):
                with self._lock:
                    fut.cancel()

            def cancels_timer(self, timer):
                with self._lock:
                    timer.cancel()
    """
    found = _findings(tmp_path, {"futmod.py": src}, "DABT102")
    symbols = sorted(f.symbol for f in found)
    # timer.cancel() is NOT future-shaped — the near-miss stays clean
    assert symbols == ["Box.cancels_future", "Box.via_helper"]
    via = next(f for f in found if f.symbol == "Box.via_helper")
    assert "helper()" in via.detail


# --------------------------------------------------------------------- DABT103
def test_dabt103_blocking_in_async(tmp_path):
    src = """
        import asyncio
        import subprocess
        import threading
        import time

        import requests

        _lk = threading.Lock()

        async def bad_sleep():
            time.sleep(0.1)

        async def bad_http():
            return requests.get("http://x")

        async def bad_subprocess():
            subprocess.run(["true"])

        async def bad_acquire():
            _lk.acquire()

        async def good():
            await asyncio.sleep(0.1)
            _lk.acquire(timeout=1.0)
            _lk.acquire(False)           # try-acquire: cannot block
            _lk.acquire(blocking=False)  # same, keyword form

            def sync_helper():
                time.sleep(1.0)  # nested sync def: not the loop's problem

            return sync_helper
    """
    found = _findings(tmp_path, {"amod.py": src}, "DABT103")
    assert sorted(f.symbol for f in found) == [
        "bad_acquire",
        "bad_http",
        "bad_sleep",
        "bad_subprocess",
    ]


# --------------------------------------------------------------------- DABT104
def test_dabt104_hot_path_reachability_and_taint(tmp_path):
    src = """
        import jax.numpy as jnp

        def _gather(y):
            return y.item()

        def decode_step(x):
            y = jnp.sum(x)
            return _gather(y)

        def cold_path(x):
            y = jnp.sum(x)
            return float(y)

        def decode_step_taint(x):
            y = jnp.sum(x)
            n = float(len([1]))
            return float(y), n
    """
    found = _findings(tmp_path, {"hot.py": src}, "DABT104")
    by_symbol = {f.symbol: f for f in found}
    # .item() flagged in the helper REACHED from decode_step, not at the root
    assert "_gather" in by_symbol
    assert "reachable from hot path decode_step" in by_symbol["_gather"].detail
    # float() fires on the tainted value only; float(len(...)) is clean
    assert "decode_step_taint" in by_symbol
    assert sum(f.symbol == "decode_step_taint" for f in found) == 1
    # cold_path is not in the registry: no finding
    assert "cold_path" not in by_symbol


def test_dabt104_aliased_numpy_import_still_caught(tmp_path):
    src = """
        import numpy as _np

        def decode_step(x):
            return _np.asarray(x)

        def unaliased_helper(x):
            return x
    """
    found = _findings(tmp_path, {"hot.py": src}, "DABT104")
    # the alias canonicalizes through the import table: still convicted
    assert [f.symbol for f in found] == ["decode_step"]
    assert "_np.asarray()" in found[0].detail


def test_dabt104_obs_recorder_entry_points_are_roots(tmp_path):
    """The observability recorders (serving/obs.py) are DABT104 roots in
    their own right: a device sync smuggled into metric recording — or into
    a helper only the recorder reaches — is convicted even when no engine
    hot path in the analyzed set calls it."""
    src = """
        import numpy as np

        def _leak(v):
            return v.item()

        class EngineObs:
            def on_tick(self, block_s, active):
                return np.asarray(block_s)

            def on_finish(self, req):  # NOT a hot-path root: lifecycle only
                return np.asarray(req)

        class Histogram:
            def observe(self, v):
                return _leak(v)

        class FlightRecorder:
            def record(self, event):
                return np.asarray(event)
    """
    found = _findings(tmp_path, {"obs_fixture.py": src}, "DABT104")
    by_symbol = {f.symbol for f in found}
    assert "EngineObs.on_tick" in by_symbol
    assert "FlightRecorder.record" in by_symbol
    # the sync reached THROUGH Histogram.observe is attributed to the helper
    assert "_leak" in by_symbol
    roots = {f.symbol: f.detail for f in found}
    assert "Histogram.observe" in roots["_leak"]
    # request-lifecycle methods are off the tick path and stay unflagged
    assert "EngineObs.on_finish" not in by_symbol


@pytest.mark.parametrize(
    "relpath, pattern",
    [
        ("serving/obs.py", "*_PhaseSpan.__enter__"),
        ("serving/obs.py", "*_PhaseSpan.__exit__"),
        ("serving/obs.py", "*LoopLedger.span"),
        ("serving/obs.py", "*LoopLedger.seconds"),
        ("serving/obs.py", "*LoopLedger.note_dispatch"),
        ("serving/obs.py", "*LoopLedger.note_marker"),
        ("serving/server.py", "*_StreamLag.note"),
    ],
)
def test_loop_ledger_entry_points_are_registered_hot_path_roots(relpath, pattern):
    """The span enter/exit pair runs around every dispatch and result wait of
    the engine loop, the stream-lag stamp between a token and its write: each
    registry pattern names a real function (a rename would silently un-root
    it), and the shipped modules stay DABT105-disciplined."""
    import fnmatch

    from dabtlint.checks import HOT_PATH_PATTERNS, _module_has_clock_convention
    from dabtlint.project import Project

    path = REPO_ROOT / "django_assistant_bot_tpu" / relpath
    (mod,) = Project.load([str(path)]).modules
    assert pattern in HOT_PATH_PATTERNS
    assert any(fnmatch.fnmatch(q, pattern) for q in mod.functions), sorted(mod.functions)[:40]
    assert _module_has_clock_convention(mod)


def test_real_obs_module_is_hot_path_clean_and_clock_disciplined():
    """The shipped serving/obs.py: its recorder entry points are in the
    hot-path registry and the module carries the DABT105 injectable-clock
    convention — so the gate (0 new findings) actively covers it."""
    import ast

    from dabtlint.checks import HOT_PATH_PATTERNS, _module_has_clock_convention
    from dabtlint.project import Project

    obs_path = REPO_ROOT / "django_assistant_bot_tpu" / "serving" / "obs.py"
    proj = Project.load([str(obs_path)])
    (mod,) = proj.modules
    # DABT105 scope: serving/ dir + the opt-in convention both hold
    assert _module_has_clock_convention(mod)
    # the registry names real entry points (a rename would silently un-root
    # the recorder; this pins pattern <-> method agreement)
    import fnmatch

    qualnames = set(mod.functions)
    for pat in (
        "*EngineObs.on_tick",
        "*Histogram.observe",
        "*FlightRecorder.record",
    ):
        assert any(fnmatch.fnmatch(q, pat) for q in qualnames), pat
    assert any(pat == "*EngineObs.on_tick" for pat in HOT_PATH_PATTERNS)
    # and the module itself contains no raw time.time()/monotonic() CALLS
    # (injectable defaults are attribute references, not calls)
    tree = ast.parse(obs_path.read_text())
    raw_calls = [
        n
        for n in ast.walk(tree)
        if isinstance(n, ast.Call)
        and isinstance(n.func, ast.Attribute)
        and isinstance(n.func.value, ast.Name)
        and n.func.value.id == "time"
    ]
    assert raw_calls == []


def test_real_fleet_module_is_clock_disciplined_for_dabt105():
    """The fleet wire (serving/fleet.py): PeerClient's connect-retry backoff
    and the router's TTL/reconcile timing are injectable — the module opts
    into the DABT105 convention and the real sweep convicts nothing in it,
    which is what lets the chaos bench drive partitions, backoff, and
    registry TTLs on an offset clock with zero wall sleeps."""
    import ast

    from dabtlint.checks import _module_has_clock_convention
    from dabtlint.project import Project

    fleet_path = REPO_ROOT / "django_assistant_bot_tpu" / "serving" / "fleet.py"
    proj = Project.load([str(fleet_path)])
    (mod,) = proj.modules
    assert _module_has_clock_convention(mod)
    # the retry/backoff and partition-tolerance surfaces under the sweep
    # really exist (a rename would silently un-cover them)
    qualnames = set(mod.functions)
    for want in (
        "PeerClient._request",
        "PeerClient._request_once",
        "FleetRouter._note_refresh_failure",
        "FleetRouter._poll_prefix",
    ):
        assert any(q.endswith(want) for q in qualnames), want
    # the REAL serving-dir DABT105 sweep: zero findings against fleet.py
    serving_dir = REPO_ROOT / "django_assistant_bot_tpu" / "serving"
    found = [
        f
        for f in run_analysis([str(serving_dir)], select={"DABT105"})
        if f.module.endswith("fleet.py")
    ]
    assert found == []
    # and no raw time.time()/monotonic()/sleep() CALLS anywhere in the
    # module — injectable defaults are attribute references, not calls
    tree = ast.parse(fleet_path.read_text())
    raw_calls = [
        n
        for n in ast.walk(tree)
        if isinstance(n, ast.Call)
        and isinstance(n.func, ast.Attribute)
        and isinstance(n.func.value, ast.Name)
        and n.func.value.id == "time"
    ]
    assert raw_calls == []


# --------------------------------------------------------------------- DABT105
def test_dabt105_convention_and_dir_scoping(tmp_path):
    files = {
        "serving/ticker.py": """
            import time

            class Ticker:
                def __init__(self, clock=time.monotonic):
                    self._clock = clock

                def stamp(self):
                    return time.monotonic()

                def good(self):
                    return self._clock()
        """,
        # serving module WITHOUT the convention: not yet disciplined, clean
        "serving/legacy.py": """
            import time

            def stamp():
                return time.time()
        """,
        # convention module OUTSIDE serving/: out of scope, clean
        "elsewhere.py": """
            import time

            def run(clock=time.monotonic):
                return time.monotonic()
        """,
    }
    found = _findings(tmp_path, files, "DABT105")
    assert [(f.module, f.symbol) for f in found] == [
        ("proj/serving/ticker.py", "Ticker.stamp")
    ]
    # the default-arg REFERENCE to time.monotonic is not a call: never flagged
    assert all("__init__" != f.symbol for f in found)


def test_dabt105_nested_function_reported_once(tmp_path):
    src = """
        import time

        class Engine:
            def __init__(self, clock=time.monotonic):
                self._clock = clock

            def outer(self):
                def inner():
                    return time.monotonic()

                return inner
    """
    found = _findings(tmp_path, {"serving/e.py": src}, "DABT105")
    # one site, one finding — attributed to the NESTED function that contains
    # it, not double-reported against the enclosing method too
    assert [f.symbol for f in found] == ["Engine.outer.<locals>.inner"]


def test_dabt105_bare_imported_sleep(tmp_path):
    src = """
        from time import sleep

        def pause(sleep=sleep):
            sleep(1.0)

        def raw_pause():
            sleep(1.0)
    """
    found = _findings(tmp_path, {"serving/p.py": src}, "DABT105")
    assert {f.symbol for f in found} == {"pause", "raw_pause"}


# ------------------------------------------------------- fixture-repo contract
def test_seeded_fixture_repo_exact_finding_set(tmp_path):
    """The acceptance-criteria fixture: one violation per checker, and the
    analyzer yields EXACTLY the expected (code, module, symbol) set."""
    files = {
        "locksmod.py": ABBA_SRC,
        "futmod.py": FUT_SRC,
        "amod.py": """
            import time

            async def leak():
                time.sleep(0.5)
        """,
        "hot.py": """
            import jax.numpy as jnp

            def decode_step(x):
                return jnp.sum(x).item()
        """,
        "serving/clockmod.py": """
            import time

            def wait(sleep=time.sleep):
                time.sleep(0.1)
        """,
    }
    found = run_analysis([str(_project(tmp_path, files))])
    assert {(f.code, f.module, f.symbol) for f in found} == {
        ("DABT101", "proj/locksmod.py", "ab"),
        ("DABT102", "proj/futmod.py", "Box.bad"),
        ("DABT103", "proj/amod.py", "leak"),
        ("DABT104", "proj/hot.py", "decode_step"),
        ("DABT105", "proj/serving/clockmod.py", "wait"),
    }


# ------------------------------------------------------------------ suppression
def test_suppression_requires_reason(tmp_path):
    files = {
        "serving/s.py": """
            import time

            def f(clock=time.monotonic):
                t0 = time.monotonic()  # dabtlint: ignore[DABT105] bench-only stamp
                t1 = time.monotonic()  # dabtlint: ignore[DABT105]
                return t0, t1
        """
    }
    _, findings, lines = analyze_paths([str(_project(tmp_path, files))])
    kept, suppressed, problems = apply_suppressions(findings, lines)
    assert len(suppressed) == 1  # the reasoned one
    assert len(kept) == 1  # the reasonless one stays a finding
    assert problems and "without a reason" in problems[0][2]


def test_suppression_on_preceding_comment_line(tmp_path):
    files = {
        "serving/s.py": """
            import time

            def f(clock=time.monotonic):
                # dabtlint: ignore[DABT105] wall-clock log line, not logic
                return time.monotonic()
        """
    }
    _, findings, lines = analyze_paths([str(_project(tmp_path, files))])
    kept, suppressed, _ = apply_suppressions(findings, lines)
    assert kept == [] and len(suppressed) == 1


# --------------------------------------------------------------------- baseline
def test_baseline_todo_stub_rejected_and_justified_accepted(tmp_path):
    proj = _project(tmp_path, {"futmod.py": FUT_SRC})
    findings = run_analysis([str(proj)])
    assert findings
    bl_path = tmp_path / "baseline.json"
    Baseline.write(str(bl_path), findings)
    with pytest.raises(BaselineError, match="justification"):
        Baseline.load(str(bl_path))
    data = json.loads(bl_path.read_text())
    for ent in data["findings"]:
        ent["justification"] = "fixture: accepted on purpose"
    bl_path.write_text(json.dumps(data))
    bl = Baseline.load(str(bl_path))
    new, accepted, stale = bl.split(findings)
    assert new == [] and len(accepted) == len(findings) and stale == []


def test_baseline_gates_new_findings_and_reports_stale(tmp_path):
    bl_path = tmp_path / "baseline.json"
    bl_path.write_text(
        json.dumps(
            {
                "findings": [
                    {
                        "code": "DABT102",
                        "module": "proj/other.py",
                        "symbol": "gone",
                        "detail": "no longer exists",
                        "justification": "stale on purpose",
                    }
                ],
                "witness": {},
            }
        )
    )
    proj = _project(tmp_path, {"futmod.py": FUT_SRC})
    findings = run_analysis([str(proj)])
    bl = Baseline.load(str(bl_path))
    new, accepted, stale = bl.split(findings)
    assert len(new) == len(findings) and accepted == []
    assert len(stale) == 1 and stale[0]["symbol"] == "gone"


def test_baseline_identity_survives_line_drift(tmp_path):
    proj = _project(tmp_path, {"futmod.py": FUT_SRC})
    key_before = run_analysis([str(proj)])[0].key
    shifted = "# a new header comment\n\n" + (proj / "futmod.py").read_text()
    (proj / "futmod.py").write_text(shifted)
    key_after = run_analysis([str(proj)])[0].key
    assert key_before == key_after  # (code, module, symbol, detail): no lines


# -------------------------------------------------------------------------- CLI
def test_cli_exit_codes(tmp_path):
    proj = _project(tmp_path, {"futmod.py": FUT_SRC})
    env = dict(os.environ, PYTHONPATH=str(TOOLS))
    r = subprocess.run(
        [sys.executable, "-m", "dabtlint", str(proj), "--no-baseline"],
        capture_output=True,
        text=True,
        env=env,
        timeout=120,
    )
    assert r.returncode == 1
    assert "DABT102" in r.stdout and "fix:" in r.stdout
    # write a baseline, justify it, and the gate goes green
    bl = tmp_path / "bl.json"
    subprocess.run(
        [sys.executable, "-m", "dabtlint", str(proj), "--baseline", str(bl), "--write-baseline"],
        capture_output=True,
        text=True,
        env=env,
        timeout=120,
        check=True,
    )
    data = json.loads(bl.read_text())
    for ent in data["findings"]:
        ent["justification"] = "cli fixture acceptance"
    bl.write_text(json.dumps(data))
    r2 = subprocess.run(
        [sys.executable, "-m", "dabtlint", str(proj), "--baseline", str(bl)],
        capture_output=True,
        text=True,
        env=env,
        timeout=120,
    )
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert "0 new findings" in r2.stdout


def test_real_tree_gate_is_green():
    """`dabtlint django_assistant_bot_tpu/` exits 0 on the committed tree —
    the same invocation CI gates on, with the checked-in baseline."""
    r = subprocess.run(
        [
            sys.executable,
            "-m",
            "dabtlint",
            str(REPO_ROOT / "django_assistant_bot_tpu"),
        ],
        capture_output=True,
        text=True,
        env=dict(os.environ, PYTHONPATH=str(TOOLS)),
        cwd=str(REPO_ROOT),
        timeout=300,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "0 new findings" in r.stdout


# ---------------------------------------------------------------- witness: unit
def _skip_if_witness_active():
    if witness_mod._installed is not None:
        pytest.skip("global lock-order witness active (DABT_LOCK_WITNESS=1)")


def test_witness_two_thread_abba_detected_deterministically(tmp_path):
    for _ in range(3):  # deterministic: same result every run
        w = LockOrderWitness(str(tmp_path))
        a = WitnessedLock(threading.Lock(), w, "A", reentrant=False)
        b = WitnessedLock(threading.Lock(), w, "B", reentrant=False)

        def t1():
            with a:
                with b:
                    pass

        def t2():
            with b:
                with a:
                    pass

        th1 = threading.Thread(target=t1)
        th1.start()
        th1.join()
        th2 = threading.Thread(target=t2)
        th2.start()
        th2.join()
        kinds = [v.kind for v in w.violations]
        assert kinds == ["lock-order-cycle"], kinds
        assert "A" in w.violations[0].description and "B" in w.violations[0].description


def test_witness_consistent_order_is_clean(tmp_path):
    w = LockOrderWitness(str(tmp_path))
    a = WitnessedLock(threading.Lock(), w, "A", reentrant=False)
    b = WitnessedLock(threading.Lock(), w, "B", reentrant=False)
    for _ in range(4):
        with a:
            with b:
                pass
    assert w.violations == []
    assert w.stats()["order_edges"] == 1


def test_witness_same_class_nesting_flagged(tmp_path):
    w = LockOrderWitness(str(tmp_path))
    s1 = WitnessedLock(threading.Lock(), w, "Sched._lock", reentrant=False)
    s2 = WitnessedLock(threading.Lock(), w, "Sched._lock", reentrant=False)
    with s1:
        with s2:
            pass
    assert [v.kind for v in w.violations] == ["same-class-nesting"]


def test_witness_rlock_reentry_is_clean(tmp_path):
    w = LockOrderWitness(str(tmp_path))
    r = WitnessedLock(threading.RLock(), w, "R", reentrant=True)
    with r:
        with r:
            pass
    with r:
        pass
    assert w.violations == [] and w.held_classes() == []


def test_witness_nonblocking_reacquire_not_a_self_deadlock(tmp_path):
    w = LockOrderWitness(str(tmp_path))
    lk = WitnessedLock(threading.Lock(), w, "L", reentrant=False)
    with lk:
        assert lk.acquire(False) is False  # try-acquire: legal, returns False
        assert lk.acquire(blocking=False) is False
    assert w.violations == [] and w.held_classes() == []
    # the BLOCKING re-acquire shape IS convicted (checked on a fresh witness
    # without actually deadlocking: note_acquire records before blocking)
    w2 = LockOrderWitness(str(tmp_path))
    w2.note_acquire("L", 1, reentrant=False)
    w2.note_acquire("L", 1, reentrant=False, blocking=True)
    assert [v.kind for v in w2.violations] == ["self-deadlock"]


def test_witness_failed_cancel_under_lock_not_convicted(tmp_path):
    _skip_if_witness_active()
    w = LockOrderWitness(str(tmp_path))
    install(w)
    try:
        lk = WitnessedLock(threading.Lock(), w, "L", reentrant=False)
        done = Future()
        done.set_result(1)
        with lk:
            assert done.cancel() is False  # runs no callbacks: hazard-free
        assert w.violations == []
        with lk:
            fresh = Future()
            assert fresh.cancel() is True  # this one DOES run callbacks
        assert [v.kind for v in w.violations] == ["future-under-lock"]
    finally:
        uninstall()


def test_witness_future_under_lock_and_allowlist(tmp_path):
    _skip_if_witness_active()
    w = LockOrderWitness(
        str(tmp_path), allowed_held={"Allowed._lock": "fixture: engine-thread lock"}
    )
    install(w)
    try:
        bad = WitnessedLock(threading.Lock(), w, "Bad._lock", reentrant=False)
        ok = WitnessedLock(threading.Lock(), w, "Allowed._lock", reentrant=False)
        with ok:
            Future().set_result(1)  # allowlisted class: clean
        assert w.violations == []
        with bad:
            Future().set_result(1)
        assert [v.kind for v in w.violations] == ["future-under-lock"]
        assert "Bad._lock" in w.violations[0].description
        # resolution with nothing held: clean
        n = len(w.violations)
        Future().set_result(2)
        assert len(w.violations) == n
    finally:
        uninstall()


# ---------------------------------------------- witness + static: same fixture
def test_abba_fixture_convicted_by_both_static_and_witness(tmp_path):
    """The acceptance contract: ONE deliberately introduced ABBA cycle, caught
    by the static DABT101 pass on the source AND by the runtime witness when
    the same module actually executes on two threads."""
    _skip_if_witness_active()
    proj = _project(tmp_path, {"abba_fixture.py": ABBA_SRC})
    static = [f for f in run_analysis([str(proj)]) if f.code == "DABT101"]
    assert len(static) == 1 and "lock_a" in static[0].detail

    w = install(LockOrderWitness(str(proj)))
    try:
        spec = importlib.util.spec_from_file_location(
            "abba_fixture_runtime", proj / "abba_fixture.py"
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)  # module-level Lock() calls get wrapped
        th1 = threading.Thread(target=mod.ab)
        th1.start()
        th1.join()
        th2 = threading.Thread(target=mod.ba)
        th2.start()
        th2.join()
    finally:
        uninstall()
    kinds = [v.kind for v in w.violations]
    assert kinds == ["lock-order-cycle"], kinds
    # lock classes are named from their creation sites in the fixture file
    assert "abba_fixture.py::lock_a" in w.violations[0].description


# ------------------------------------------------------------- witness: plugin
def test_witness_plugin_fails_session_on_violation(tmp_path):
    """End-to-end pytest wiring: the test itself PASSES, but the witness
    plugin fails the session at sessionfinish with its summary."""
    proj = tmp_path / "wproj"
    proj.mkdir()
    (proj / "test_abba_plugin.py").write_text(
        textwrap.dedent(
            """
            import threading

            lock_a = threading.Lock()
            lock_b = threading.Lock()

            def test_abba_order():
                def t1():
                    with lock_a:
                        with lock_b:
                            pass

                def t2():
                    with lock_b:
                        with lock_a:
                            pass

                a = threading.Thread(target=t1); a.start(); a.join()
                b = threading.Thread(target=t2); b.start(); b.join()
            """
        )
    )
    env = dict(
        os.environ,
        PYTHONPATH=str(TOOLS),
        DABT_LOCK_WITNESS="1",
        DABT_WITNESS_ROOT=str(proj),
    )
    r = subprocess.run(
        [
            sys.executable,
            "-m",
            "pytest",
            str(proj / "test_abba_plugin.py"),
            "-q",
            "-p",
            "dabtlint.witness",
            "-p",
            "no:cacheprovider",
            "-p",
            "no:xdist",
            "-p",
            "no:randomly",
        ],
        capture_output=True,
        text=True,
        env=env,
        cwd=str(tmp_path),
        timeout=180,
    )
    assert "1 passed" in r.stdout  # the test itself is green...
    assert r.returncode != 0, r.stdout  # ...the witness fails the session
    assert "lock-order witness" in r.stdout
    assert "lock-order-cycle" in r.stdout


def test_witness_plugin_clean_session_stays_green(tmp_path):
    proj = tmp_path / "cproj"
    proj.mkdir()
    (proj / "test_clean_plugin.py").write_text(
        textwrap.dedent(
            """
            import threading

            lock_a = threading.Lock()
            lock_b = threading.Lock()

            def test_single_order():
                with lock_a:
                    with lock_b:
                        pass
            """
        )
    )
    env = dict(
        os.environ,
        PYTHONPATH=str(TOOLS),
        DABT_LOCK_WITNESS="1",
        DABT_WITNESS_ROOT=str(proj),
    )
    r = subprocess.run(
        [
            sys.executable,
            "-m",
            "pytest",
            str(proj / "test_clean_plugin.py"),
            "-q",
            "-p",
            "dabtlint.witness",
            "-p",
            "no:cacheprovider",
            "-p",
            "no:xdist",
            "-p",
            "no:randomly",
        ],
        capture_output=True,
        text=True,
        env=env,
        cwd=str(tmp_path),
        timeout=180,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "lock-order witness" in r.stdout
    assert "0 violation(s)" in r.stdout
