"""The process tree below a benchmark run: who is there, killing what is
left, and a watchdog. Copied from `chip_smoke.py` (PR 21), the only code of
this kind that has run on the chip; the benchmark imports nothing from it."""

import faulthandler
import os
import signal
import sys
import threading
import time


def descendants(root: int) -> dict[int, int]:
    """{pid: depth below ``root``} of every live descendant (Linux /proc)."""
    children: dict[int, list[int]] = {}
    for entry in os.listdir("/proc"):
        if entry.isdigit():
            try:
                with open(f"/proc/{entry}/stat") as f:
                    fields = f.read().rsplit(")", 1)[1].split()
            except OSError:
                continue  # exited while we looked
            if fields[0] != "Z":
                children.setdefault(int(fields[1]), []).append(int(entry))
    out: dict[int, int] = {}
    stack = [(root, 0)]
    while stack:
        pid, depth = stack.pop()
        for child in children.get(pid, []):
            out[child] = depth + 1
            stack.append((child, depth + 1))
    return out


def actor_pids() -> list[int]:
    """The store's actor processes: forked BY multiprocessing's fork server,
    so they sit two levels below this process."""
    return sorted(p for p, depth in descendants(os.getpid()).items() if depth >= 2)


def kill(pids) -> list[str]:
    """SIGKILL ``pids`` and wait until they are gone. Returns one
    ``pid: command line`` per process that was still there to kill."""
    killed = []
    for pid in pids:
        try:
            with open(f"/proc/{pid}/cmdline") as f:
                cmd = f.read().replace("\0", " ").strip()
            os.kill(pid, signal.SIGKILL)
        except OSError:
            continue  # exited while we looked
        killed.append(f"{pid}: {cmd}")
    deadline = time.monotonic() + 10.0
    while set(pids) & set(descendants(os.getpid())) and time.monotonic() < deadline:
        time.sleep(0.05)
    return killed


def leave_no_process() -> bool:
    """Nothing a run started may outlive it. ``ts.shutdown()`` stops the
    actors but keeps multiprocessing's fork server (and its resource tracker)
    warm; left alone they exit only AFTER this process has. Stop and reap
    them, and kill whatever a failed run left behind. False if anything had
    to be killed or is still there."""
    from torchstore_tpu.runtime import stop_spawn_helpers

    # Actors first: a live one holds the resource tracker's pipe open, and
    # stopping the tracker would wait for it.
    killed = kill(actor_pids())
    stop_spawn_helpers()
    killed += kill(list(descendants(os.getpid())))
    for line in killed:
        print(f"chipbench: had to kill {line}", file=sys.stderr)
    left = descendants(os.getpid())
    if left:
        print(f"chipbench: processes still running: {sorted(left)}", file=sys.stderr)
    return not killed and not left


def arm_watchdog(seconds: float) -> None:
    """A call that blocks inside the runtime cannot be cancelled from Python:
    past ``seconds`` every thread's stack goes to stderr, every child is
    killed and the process exits non-zero with no result line."""

    def abort() -> None:
        faulthandler.dump_traceback(all_threads=True)
        kill(list(descendants(os.getpid())))
        os._exit(1)

    timer = threading.Timer(seconds, abort)
    timer.daemon = True
    timer.start()
    # Should a call hold the GIL for ever, the timer never runs: this one
    # needs no GIL (but cannot stop the children).
    faulthandler.dump_traceback_later(seconds + 30, exit=True)
