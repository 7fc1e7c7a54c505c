"""Reductions of the program's own host timing, for the metric readers.

``ServingEngine(..., spans=True)`` keeps ``RunMetrics.spans``: records
with ``name``, ``t0``, ``t1`` (raw ``perf_counter``, the clock the device
trace is moved onto), ``rid``, ``tid`` and ``cause``.  A traced run
carries them as ``run.spans``, and each request of the window its token
stamps (``Request.t_tokens``) as ``tokens``.  A run without them (an
untraced run, or a program that makes none) gives None here, and its
metrics are left out.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from chipbench import stats
from chipbench import trace as tr

LAUNCH = ("prefill.launch", "decode.launch")
DECISION = ("wake", "place", "commit")
# the serving engine's device programs, as a trace's module events name
# them
PREFILL_PROGRAM = "jit_serve_prefill"
DECODE_PROGRAM = "jit_serve_decode"


def in_window(run, *names: str) -> Optional[list]:
    """The spans named ``names`` that start in the window; None where the
    run carries no spans."""
    spans = run.spans
    if spans is None:
        return None
    lo, hi = run.window
    return [s for s in spans if s.name in names and lo <= s.t0 < hi]


def mean_ms(run, name: str) -> Optional[float]:
    """Mean length of the window's ``name`` spans, in ms."""
    spans = in_window(run, name)
    m = stats.mean(s.t1 - s.t0 for s in spans or ())
    return None if m is None else 1e3 * m


def per_task_us(run, names: tuple) -> Optional[float]:
    """Mean over the tasks committed in the window of the summed length
    of their ``names`` spans (matched by tid), in us."""
    committed = in_window(run, "commit")
    if not committed:
        return None
    tids = {s.tid for s in committed}
    total = sum(s.t1 - s.t0 for s in run.spans
                if s.name in names and s.tid in tids)
    return 1e6 * total / len(tids)


def inter_token_s(run) -> Optional[list[float]]:
    """The gaps between consecutive token stamps of every request of the
    window; an unfinished request adds one missing gap (inf).  None where
    the requests carry no stamps (a finished request without any)."""
    out: list[float] = []
    for r in run.requests:
        if not r.finished:
            out.append(float("inf"))
        elif not r.tokens:
            return None
        else:
            out.extend(np.diff(r.tokens).tolist())
    return out


def program_events(run, program: str) -> Optional[list]:
    """The window's ``XLA Modules`` events of the jitted function
    ``program`` (``jit_<name>(<fingerprint>)``); None without a trace."""
    if run.events is None:
        return None
    lo, hi = run.window
    return [e for e in tr.modules(run.events)
            if e.name.split("(")[0] == program and lo <= e.t0 < hi]


def idle_in_launch_pct(run) -> Optional[float]:
    """Share of the window in which the device is idle while the host is
    inside a step's launch: idle gaps whose midpoint lies in a
    ``prefill.launch`` or ``decode.launch`` span, in %."""
    spans = run.spans
    ops = tr.ops(run.events) if run.events is not None else []
    if spans is None or not ops:
        return None
    lo, hi = run.window
    idle = tr.gaps(ops, lo, hi)
    mids = (idle[:, 0] + idle[:, 1]) / 2
    hit = tr.covered(mids, [(s.t0, s.t1) for s in spans
                            if s.name in LAUNCH])
    return 100.0 * float((idle[hit, 1] - idle[hit, 0]).sum()) / (hi - lo)
