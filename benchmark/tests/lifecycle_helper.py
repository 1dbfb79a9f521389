"""A parent for test_lifecycle.py: builds one ``launcher.Child`` (the
router in front of a backend that is not there: it starts within a
second and imports no JAX), prints its pid, and waits to be ended.

    lifecycle_helper.py <workdir> idle|busy

``idle`` installs no handler: the kernel's death signal alone has to end
the child. ``busy`` is ``run.py`` in small: under ``lifecycle.guarded``,
with a ``finally`` that stops the child, and inside an event loop whose
tasks never leave it idle, gathered as ``client.open_loop`` gathers its
requests, so that a signal lands inside a task's step."""

import asyncio
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import launcher, lifecycle  # noqa: E402


def child(workdir: str) -> launcher.Child:
    c = launcher.Child(
        "router", ["router", "--backend", "nobody=http://127.0.0.1:9",
                   "--host", "127.0.0.1", "--port",
                   str(launcher.free_port())], dict(os.environ), workdir)
    print(c.proc.pid, flush=True)
    return c


async def spin() -> None:
    while True:
        sum(range(20000))
        await asyncio.sleep(0)


async def loop_that_is_never_idle() -> list:
    tasks = [asyncio.create_task(spin()) for _ in range(4)]
    return await asyncio.gather(*tasks, return_exceptions=True)


def busy(timeline: lifecycle.Timeline) -> int:
    c = child(sys.argv[1])
    try:
        timeline.enter("spin")
        print("a result line:", asyncio.run(loop_that_is_never_idle()))
        return 0
    finally:
        c.stop(hard=True)


if __name__ == "__main__":
    if sys.argv[2] == "busy":
        sys.exit(lifecycle.guarded(
            busy, lifecycle.Timeline(time.monotonic(), "helper")))
    child(sys.argv[1])
    time.sleep(600)
