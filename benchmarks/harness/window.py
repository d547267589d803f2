"""The measured window and the arithmetic of its rates.

The window runs whole jobs back to back.  No job starts once `seconds`
have gone; the window closes when the job in flight returns, and every rate
is over that real length.  Nothing inside is left out.
"""

from __future__ import annotations

import time
from typing import Callable


def run_window(job: Callable[[], float], seconds: float,
               clock: Callable[[], float] = time.perf_counter,
               max_jobs: int | None = None) -> dict:
    """Call `job()` (which returns its units of work, and only once the
    work is on the host) until `seconds` have gone or `max_jobs` are done.
    Returns {"window_s", "jobs", "work", "ends"}: `ends` are the
    completion times from the window's start."""
    ends, work = [], 0.0
    t0 = clock()
    while True:
        work += job()
        now = clock() - t0
        ends.append(now)
        if now >= seconds or (max_jobs is not None and len(ends) >= max_jobs):
            break
    return {"window_s": ends[-1], "jobs": len(ends), "work": work,
            "ends": ends}


def rates(window: dict) -> dict:
    """All work over all time, and the window's seconds per job."""
    return {"work_per_s": window["work"] / window["window_s"],
            "s_per_job": window["window_s"] / window["jobs"]}
