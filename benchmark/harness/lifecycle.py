"""No run leaves a process behind: when the benchmark's process is gone,
every process it started is gone within seconds, whether it returned,
raised, or was ended by SIGTERM, SIGHUP, SIGINT or SIGKILL.

Three things hold that. ``spawn`` starts each child through
``die_with_parent.py``, so the kernel kills it when this process dies:
the only one that survives ``kill -9``. ``guarded`` turns the signals
that can be caught into an exception raised in the main thread, so every
``finally`` on the stack runs and the run says where it was ended. And
whoever owns a child stops it in such a ``finally`` (``cell.served``),
hard on any way out but the normal one.
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import time

_SHIM = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "die_with_parent.py")
HAS_DEATH_SIGNAL = sys.platform.startswith("linux")
CAUGHT = (signal.SIGTERM, signal.SIGHUP, signal.SIGINT)


class Ended(KeyboardInterrupt):
    """A signal asked this process to end. A KeyboardInterrupt, because
    that (with SystemExit) is what asyncio lets through: raised inside a
    task's step or a callback of the open loop, any other BaseException
    is stored on the task, and a ``gather(return_exceptions=True)``
    then loses it, with the signals already ignored."""

    def __init__(self, signum: int):
        super().__init__(signum)
        self.signum = signum


def spawn(command: list, **popen_kw) -> subprocess.Popen:
    """``Popen(command)``, killed by the kernel when this process dies.
    The death signal follows the thread that forked: call this from the
    main thread only, or the child dies with a worker thread."""
    return subprocess.Popen(
        [sys.executable, _SHIM, str(os.getpid()), *command], **popen_kw)


def reap(proc: "subprocess.Popen | None") -> None:
    """Kill ``proc`` if it still runs, and wait for it."""
    if proc is not None and proc.poll() is None:
        proc.kill()
        proc.communicate()


class Timeline:
    """The phases a run has entered, each with its start on the run's
    clock: what tells a run that was cut which limit it hit."""

    def __init__(self, t0: float, who: str = "benchmark"):
        self.t0 = t0
        self.who = who
        self.phases: list = [("start", t0)]

    def enter(self, name: str, **then: float) -> None:
        """The phase ``name`` begins now. ``then`` (phase=seconds from
        now) are the phases that follow by the clock alone (the parts of
        the open loop, where nothing is added to say so); the next
        ``enter`` forgets those that had not begun."""
        now = time.monotonic()
        self.phases = [p for p in self.phases if p[1] <= now]
        self.phases.append((name, now))
        self.phases += sorted(((n, now + s) for n, s in then.items()),
                              key=lambda p: p[1])
        print(f"{self.who}: phase {name} +{now - self.t0:.1f} s",
              file=sys.stderr, flush=True)

    def ended_by(self, signum: int) -> str:
        now = time.monotonic()
        begun = [p for p in self.phases if p[1] <= now]
        line = " ".join(f"{name}+{at - self.t0:.1f}" for name, at in begun)
        return (f"{self.who}: ended by signal {signum} after "
                f"{now - self.t0:.1f} s in phase {begun[-1][0]} ({line})")


def guarded(main, timeline: Timeline) -> int:
    """Run ``main(timeline)`` with SIGTERM, SIGHUP and SIGINT raising
    ``Ended`` in the main thread. A run so ended has stopped its children
    on the way out (their ``finally``); it says by which signal, when and
    in which phase, prints no result and exits 128 + the signal's number
    at once, without waiting for a thread that is still inside a request."""

    def raise_ended(signum, frame):
        for s in CAUGHT:        # the way out is not interrupted again:
            signal.signal(s, signal.SIG_IGN)    # SIGKILL still ends it
        raise Ended(signum)

    for s in CAUGHT:
        signal.signal(s, raise_ended)
    try:
        return main(timeline)
    except Ended as e:
        print(timeline.ended_by(e.signum), file=sys.stderr, flush=True)
        sys.stdout.flush()
        os._exit(128 + e.signum)
