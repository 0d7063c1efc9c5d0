"""Did the machine freeze? A canary says so in every run, and ``--freeze``
makes one happen.

The canary is a child process that does nothing but ``sleep(0.01)`` in a loop
and appends every gap between two wake-ups of over 50 ms (``monotonic`` start,
length) to a file in the run directory. It runs none of the program and
shares no interpreter with the server or the load generator, so a gap it sees
is the machine's: every process was held, not one loop of one of them. What
it saw inside the window is reported (``machine_freeze_ms``, the sum, and
``machine_freeze_max_ms``) and decides nothing: no request and no run is left
out because of it.

The freezer (``run.py --freeze AT:SECONDS``; calibration, no result takes it)
is a second child that stops the given processes and process groups ``AT``
seconds into the window (SIGSTOP), sleeps, and lets them go on (SIGCONT): the
server's group, the load generator and the canary, as a freeze of the machine
would hold them.

    python harness/machine.py canary <file>
    python harness/machine.py freeze <at_monotonic> <seconds> <pgid,...> <pid,...>
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import time

TICK_S = 0.01
GAP_S = 0.05
GAPS_FILE = "canary.txt"  # in the run directory


def canary(path: str) -> None:
    parent = os.getppid()
    with open(path, "a", buffering=1) as f:
        last = time.monotonic()
        while os.getppid() == parent:  # a run that was killed leaves no canary behind
            time.sleep(TICK_S)
            now = time.monotonic()
            if now - last > GAP_S:
                f.write(f"{last:.6f} {now - last:.6f}\n")
            last = now


def freeze(at: float, seconds: float, pgids: list, pids: list) -> None:
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(1))  # ended early: still let them go on
    time.sleep(max(0.0, at - time.monotonic()))
    try:
        for g in pgids:
            os.killpg(g, signal.SIGSTOP)
        for p in pids:
            os.kill(p, signal.SIGSTOP)
        time.sleep(seconds)
    finally:
        for p in pids:
            os.kill(p, signal.SIGCONT)
        for g in pgids:
            os.killpg(g, signal.SIGCONT)


def start(*argv) -> subprocess.Popen:
    """One of the two children, in a session of its own."""
    return subprocess.Popen([sys.executable, os.path.abspath(__file__), *map(str, argv)],
                            stdout=subprocess.DEVNULL, start_new_session=True)


def stop(proc: subprocess.Popen | None) -> None:
    if proc is None or proc.poll() is not None:
        return
    proc.terminate()
    try:
        proc.wait(timeout=5)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()


def read_gaps(path) -> list:
    """[(start, length)] in seconds, as the canary wrote them; a last line cut
    short by its end is left out."""
    gaps = []
    try:
        with open(path) as f:
            for ln in f:
                parts = ln.split()
                if len(parts) == 2 and ln.endswith("\n"):
                    gaps.append((float(parts[0]), float(parts[1])))
    except OSError:
        pass
    return gaps


def reduce(gaps: list, t_start: float, t_end: float) -> dict:
    """The part of every gap that lies inside [t_start, t_end) (``monotonic``
    seconds), summed, and the longest such part, in ms."""
    inside = [min(s + n, t_end) - max(s, t_start) for s, n in gaps]
    inside = [x for x in inside if x > 0]
    return {"machine_freeze_ms": sum(inside) * 1e3, "machine_freeze_max_ms": max(inside, default=0.0) * 1e3,
            "machine_freezes": len(inside)}


def seen(run_dir, t_start: float, t_end: float) -> dict:
    """What the run's canary saw inside the window."""
    return reduce(read_gaps(os.path.join(run_dir, GAPS_FILE)), t_start, t_end)


if __name__ == "__main__":
    if sys.argv[1] == "canary":
        canary(sys.argv[2])
    elif sys.argv[1] == "freeze":
        ids = [[int(x) for x in a.split(",") if x] for a in sys.argv[4:6]]
        freeze(float(sys.argv[2]), float(sys.argv[3]), *ids)
    else:
        sys.exit(f"unknown mode {sys.argv[1]!r}")
