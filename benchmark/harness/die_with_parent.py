"""exec a command that the kernel kills when the process that started it
dies, however that process ends (``kill -9`` included):

    python die_with_parent.py <parent pid> <program> [arguments ...]

``prctl(PR_SET_PDEATHSIG, SIGKILL)`` survives the exec. A parent that died
before the call is seen by ``getppid()`` having changed. The signal follows
the THREAD that started this process, so a parent starts it from its main
thread. Where there is no ``prctl`` (not Linux) the command runs without
the guarantee. Imports nothing of the benchmark: it is a process of its
own, and gone after the exec.
"""

import os
import signal
import sys

PR_SET_PDEATHSIG = 1


def main(argv: list) -> None:
    parent, command = int(argv[1]), argv[2:]
    if sys.platform.startswith("linux"):
        import ctypes

        libc = ctypes.CDLL(None, use_errno=True)
        libc.prctl.argtypes = [ctypes.c_int] + [ctypes.c_ulong] * 4
        libc.prctl.restype = ctypes.c_int
        if libc.prctl(PR_SET_PDEATHSIG, signal.SIGKILL, 0, 0, 0) != 0:
            sys.exit(f"die_with_parent: prctl failed: "
                     f"{os.strerror(ctypes.get_errno())}")
    if os.getppid() != parent:
        sys.exit(f"die_with_parent: parent {parent} is gone")
    os.execv(command[0], command)


if __name__ == "__main__":
    main(sys.argv)
