"""A run ended from outside leaves nothing behind: SIGTERM takes ``run.py``
through its ``finally`` (the child's group is killed), SIGKILL leaves the child
to see end-of-file on its control pipe and end itself.  PR 28 was refused
because the chip's holder outlived its run and answered the next run's port.
The command as a process of its own, the rehearsal cell on the CPU."""
import json
import os
import signal
import socket
import subprocess
import sys
import time

import psutil
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
RUN = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--benchmark-json", os.path.join(HERE, "rehearsal.json"),
       "--workload", "tiny.open", "--trace", "0", "--rehearsal"]
GONE_WITHIN_S = 10.0


def _alive(pid: int) -> bool:
    try:
        return psutil.Process(pid).status() != psutil.STATUS_ZOMBIE
    except psutil.NoSuchProcess:
        return False


def _refuses(port: int) -> bool:
    with socket.socket() as s:
        s.settimeout(1.0)
        return s.connect_ex(("127.0.0.1", port)) != 0


def _start(seconds: int, stage: str):
    """``run.py`` started, and read up to the line that says ``stage``."""
    proc = subprocess.Popen(RUN + ["--seed", "17", "--seconds", str(seconds)], cwd=ROOT, text=True,
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE, start_new_session=True)
    for line in proc.stderr:
        if line.startswith(stage):
            return proc, line
    pytest.fail(f"run.py ended (rc={proc.wait()}) before {stage!r}")


@pytest.mark.parametrize("sig", [signal.SIGTERM, signal.SIGKILL], ids=["SIGTERM", "SIGKILL"])
def test_a_run_ended_inside_its_window_leaves_no_process_and_no_port(sig):
    proc, line = _start(60, "window open:")
    try:
        child = int(line.split("child ")[1].split()[0])
        port = int(line.rsplit(":", 1)[1])
        family = [child] + [p.pid for p in psutil.Process(child).children(recursive=True)]
        assert _alive(child) and not _refuses(port)  # serving, inside the window
        time.sleep(1.0)
        proc.send_signal(sig)
        t0 = time.monotonic()
        rc = proc.wait(timeout=GONE_WITHIN_S)
        while time.monotonic() - t0 < GONE_WITHIN_S and (any(map(_alive, family)) or not _refuses(port)):
            time.sleep(0.1)
        assert not any(map(_alive, family)), f"the child outlived run.py by {GONE_WITHIN_S} s"
        assert _refuses(port)
        assert rc != 0 and not [l for l in proc.stdout.read().splitlines() if l.startswith("{")]  # and no result
        if sig == signal.SIGTERM:
            assert "ended by SIGTERM" in proc.stderr.read()
    finally:
        for pid in [proc.pid] + ([child] if "child" in locals() else []):
            if _alive(pid):
                os.kill(pid, signal.SIGKILL)

    # the next run of the same cell boots, serves and ends correct
    out = subprocess.run(RUN + ["--seed", "18", "--seconds", "2"], cwd=ROOT, text=True, capture_output=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout.splitlines()[-1])["correct"] is True


def test_the_runs_own_deadline_ends_it_like_a_signal(monkeypatch, capsys):
    """A boot that never answers is ended by the run itself, under the driver's limit."""
    from benchmarks import run

    monkeypatch.setattr(run, "DEADLINE_S", 2.0)
    monkeypatch.setattr(run, "FIRST_RUN_DEADLINE_S", 2.0)
    before = signal.getsignal(signal.SIGTERM), signal.getsignal(signal.SIGALRM)
    t0 = time.monotonic()
    with pytest.raises(SystemExit, match="ended by its deadline"):
        run.main(RUN[2:] + ["--seed", "19", "--seconds", "30"])
    assert time.monotonic() - t0 < 2.0 + GONE_WITHIN_S
    assert not [l for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    assert (signal.getsignal(signal.SIGTERM), signal.getsignal(signal.SIGALRM)) == before  # the process's own are back
    assert not [p for p in psutil.Process().children(recursive=True) if _alive(p.pid)]
