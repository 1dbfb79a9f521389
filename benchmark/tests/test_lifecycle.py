"""No run leaves a process behind: whichever signal ends the parent, and
wherever it is, every process it started is gone within seconds."""

import contextlib
import glob
import os
import re
import signal
import subprocess
import sys
import time

import pytest

from harness import lifecycle, manifest

HERE = os.path.dirname(os.path.abspath(__file__))
RUN = os.path.join(manifest.BENCH_DIR, "run.py")
SIGNALS = [signal.SIGKILL, signal.SIGTERM, signal.SIGHUP]
CHILDREN = re.compile(r"^benchmark: children server=(\d+) router=(\d+)$",
                      re.M)


def sig_id(s) -> str:
    return signal.Signals(s).name


def needs_death_signal(signum) -> None:
    if signum == signal.SIGKILL and not lifecycle.HAS_DEATH_SIGNAL:
        pytest.skip("no prctl(PR_SET_PDEATHSIG) on this platform: nothing "
                    "ends the children of a parent that is killed")


def alive(pid: int) -> bool:
    """A zombie is not alive: it runs nothing and holds nothing."""
    try:
        os.kill(pid, 0)
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] != "Z"
    except ProcessLookupError:
        return False
    except OSError:         # no /proc here, or gone between the two
        return True


def still_alive_after(pids, seconds: float) -> list:
    deadline = time.monotonic() + seconds
    while time.monotonic() < deadline and any(alive(p) for p in pids):
        time.sleep(0.05)
    return [p for p in pids if alive(p)]


def wait_for(what, seconds: float, why: str):
    deadline = time.monotonic() + seconds
    while time.monotonic() < deadline:
        got = what()
        if got:
            return got
        time.sleep(0.05)
    pytest.fail(f"{why}: nothing within {seconds} s")


@contextlib.contextmanager
def started(args: list, tmp_path, pids: list):
    """``args`` as a process whose output goes to files; it and whatever
    pid the test appended to ``pids`` are killed on the way out, also when
    the test failed."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path))
    with open(tmp_path / "out", "wb") as out, \
            open(tmp_path / "err", "wb") as err:
        proc = subprocess.Popen([sys.executable, *args], env=env, stdout=out,
                                stderr=err, cwd=manifest.REPO_DIR)
        try:
            yield proc
        finally:
            proc.kill()
            proc.wait(timeout=10)
            for pid in pids:    # each leads a session of its own
                with contextlib.suppress(ProcessLookupError,
                                         PermissionError):
                    os.killpg(pid, signal.SIGKILL)


@pytest.mark.parametrize("signum", SIGNALS, ids=sig_id)
def test_a_child_dies_with_a_parent_that_handles_nothing(signum, tmp_path):
    if not lifecycle.HAS_DEATH_SIGNAL:
        pytest.skip("this parent installs no handler: only the death "
                    "signal (prctl, Linux) ends its child")
    pids: list = []
    helper = os.path.join(HERE, "lifecycle_helper.py")
    with started([helper, str(tmp_path), "idle"], tmp_path, pids) as parent:
        pids.append(int(wait_for(
            lambda: (tmp_path / "out").read_text().strip(), 10,
            "the helper's child")))
        wait_for(lambda: "llms_on_kubernetes_tpu" in open(
            f"/proc/{pids[0]}/cmdline").read(), 10, "the exec")
        parent.send_signal(signum)
        assert parent.wait(timeout=5) == -signum
        assert still_alive_after(pids, 5) == []


@pytest.mark.parametrize("signum", lifecycle.CAUGHT, ids=sig_id)
def test_a_signal_inside_a_task_step_still_ends_the_run(signum, tmp_path):
    """asyncio stores what a task's step raises on the task, unless it is
    a KeyboardInterrupt or a SystemExit; the open loop gathers its tasks
    with ``return_exceptions=True``. A loop that is never idle takes the
    signal inside a step."""
    pids: list = []
    helper = os.path.join(HERE, "lifecycle_helper.py")
    with started([helper, str(tmp_path), "busy"], tmp_path, pids) as parent:
        pids.append(int(wait_for(
            lambda: (tmp_path / "out").read_text().strip(), 10,
            "the helper's child")))
        wait_for(lambda: "helper: phase spin" in
                 (tmp_path / "err").read_text(), 10, "the busy loop")
        time.sleep(0.5)
        parent.send_signal(signum)
        assert parent.wait(timeout=10) == 128 + signum
        assert still_alive_after(pids, 5) == []
    assert re.search(rf"helper: ended by signal {int(signum)} after [\d.]+ s "
                     r"in phase spin", (tmp_path / "err").read_text())
    assert "result" not in (tmp_path / "out").read_text()


def test_phases_that_begin_by_the_clock(monkeypatch):
    now = [100.0]
    monkeypatch.setattr(lifecycle.time, "monotonic", lambda: now[0])
    line = lifecycle.Timeline(90.0)
    line.enter("preroll", window=5.0, drain=58.0, tail=55.0)
    now[0] = 130.0
    assert "after 40.0 s in phase window (start+0.0 preroll+10.0 " \
        "window+15.0)" in line.ended_by(15)
    now[0] = 156.0
    assert "in phase tail " in line.ended_by(15)
    now[0] = 120.0      # the loop failed early: what had not begun is gone
    line.enter("stop")
    now[0] = 170.0
    assert line.ended_by(1).endswith(
        "in phase stop (start+0.0 preroll+10.0 window+15.0 stop+30.0)")


def test_a_child_whose_parent_is_already_gone_does_not_start(tmp_path):
    shim = os.path.join(manifest.BENCH_DIR, "harness", "die_with_parent.py")
    marker = tmp_path / "ran"
    proc = subprocess.run(
        [sys.executable, shim, "0", sys.executable, "-c",
         f"open({str(marker)!r}, 'w')"],
        capture_output=True, text=True, timeout=20)
    assert proc.returncode != 0 and "is gone" in proc.stderr
    assert not marker.exists()


def device_line(tmp_path) -> bool:
    logs = glob.glob(str(tmp_path / "bench-*" / "server.log"))
    return bool(logs) and "[serve] devices:" in open(
        logs[0], errors="replace").read()


@pytest.mark.parametrize("when", ["importing", "device-held", "open-loop"])
@pytest.mark.parametrize("signum", SIGNALS, ids=sig_id)
def test_a_run_that_is_ended_leaves_nothing_and_says_where(signum, when,
                                                           tmp_path):
    needs_death_signal(signum)
    pids: list = []
    with started([RUN, "--rehearse", "--seconds", "4"], tmp_path,
                 pids) as run:
        m = wait_for(lambda: CHILDREN.search((tmp_path / "err").read_text()),
                     30, "the children line")
        pids.extend(int(g) for g in m.groups())
        assert all(alive(p) for p in pids)
        if when == "importing":
            time.sleep(2.0)
        elif when == "device-held":
            wait_for(lambda: device_line(tmp_path), 120, "the device line")
        else:       # inside asyncio.run, client tasks in flight
            wait_for(lambda: "benchmark: phase preroll" in
                     (tmp_path / "err").read_text(), 600, "the preroll")
            time.sleep(2.0)
        run.send_signal(signum)
        code = run.wait(timeout=15)
        assert still_alive_after(pids, 5) == []
    err = (tmp_path / "err").read_text()
    assert (tmp_path / "out").read_text() == ""     # no result line
    if signum == signal.SIGKILL:
        assert code == -signum
    else:
        assert code == 128 + signum
        assert re.search(rf"benchmark: ended by signal {int(signum)} after "
                         r"[\d.]+ s in phase (start|shapes|preroll|window) "
                         r"\(start\+0\.0",
                         err), err[-2000:]
        assert "--- last lines of server ---" in err
