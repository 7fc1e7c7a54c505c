"""Runs a cell as ``run.py`` does, with the engine's host spans on, and
reads the metrics of the program's own timing beside the cell's.

    python3 chipbench/tools/span_run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1> [--out <dir>]

The engine is built with ``spans=True``; its spans and each window
request's token stamps are put on the run (``run.spans``, each request's
``tokens``) before the readers of ``METRICS`` run, so the line carries
both the cell's metrics and these.  With ``--trace 1 --out <dir>`` it
also writes ``slice.json.gz``: the ``XLA Ops`` and ``XLA Modules`` events,
spans and token stamps from the first ``jit_serve_prefill`` program to
``--slice-s`` past its start (``chipbench/tests/data`` keeps one).
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]

# the metrics that read the spans and stamps, by loop, with their units;
# the traced ones need the device trace too
METRICS = {
    "open": [("itl_ms.p95", "ms"), ("decode_launch_ms.open", "ms"),
             ("decode_token_wait_ms.open", "ms"),
             ("sched_decision_us.open", "us"),
             ("sched_lock_wait_us.open", "us")],
    "closed": [("decode_launch_ms.closed", "ms"),
               ("decode_token_wait_ms.closed", "ms"),
               ("sched_decision_us.closed", "us"),
               ("sched_lock_wait_us.closed", "us")],
}
TRACED = {"open": [("device_idle_launch.open", "%")], "closed": []}


def run_spanned(spec, seed: int, seconds: float, trace: bool, *,
                t_proc0: float, trace_dir=None, require_chip: bool = True):
    """``cell.run`` with the engine's spans on; returns its result, with
    the span metrics added to ``metrics``, and its info."""
    import repro.serve
    from chipbench import cell
    from chipbench.spec import reader

    real = repro.serve.ServingEngine
    kept = {}

    class Spanning(real):
        def __init__(self, *a, **k):
            super().__init__(*a, spans=True, **k)
            kept["metrics"] = self.runtime.metrics
            kept["requests"] = self.requests

    repro.serve.ServingEngine = Spanning
    try:
        result, info = cell.run(spec, seed, seconds, trace, t_proc0=t_proc0,
                                trace_dir=trace_dir,
                                require_chip=require_chip)
    finally:
        repro.serve.ServingEngine = real
    data = info["data"]
    attach(data, kept["metrics"].spans, kept["requests"])
    loop = spec.traffic["loop"]
    for name, unit in METRICS[loop] + (TRACED[loop] if trace else []):
        v = reader(name)(data)
        if v is not None and math.isfinite(v):
            result["metrics"][name] = {"value": v, "unit": unit}
        else:
            info["unread"].append(name)
    return result, info


class Stamped:
    """A window request (``cell.Req``) with its token stamps."""

    def __init__(self, req, tokens):
        self._req, self.tokens = req, tokens

    def __getattr__(self, name):
        return getattr(self._req, name)


def attach(data, spans, requests) -> None:
    """Puts the engine's spans and the window requests' token stamps on
    the run.  The window's requests are the engine's last, in order."""
    window = sorted(requests.values(), key=lambda r: r.rid)
    window = window[len(window) - len(data.requests):]
    for r, q in zip(data.requests, window):
        if r.first != q.t_first_token:
            raise ValueError(f"request {q.rid} is not the window's")
    data.requests = [Stamped(r, tuple(q.t_tokens))
                     for r, q in zip(data.requests, window)]
    data.spans = list(spans)


def write_slice(data, spec, kind: str, path: Path, slice_s: float) -> int:
    """Writes the events, spans and token stamps of the ``slice_s``
    seconds from the first prefill program's start; returns the events
    written."""
    from chipbench import spans as sp
    from chipbench import trace as tr
    pre = sp.program_events(data, sp.PREFILL_PROGRAM) or []
    lo = (pre[0].t0 if pre else data.window[0]) - 0.002
    hi = lo + slice_s
    rows = [[e.line, e.name[:400], round(e.t0 - lo, 9), round(e.dur, 9)]
            for e in data.events if lo <= e.t0 < hi
            and e.line in (tr.OPS_LINE, tr.MODULES_LINE)]
    spans = [[s.name, round(s.t0 - lo, 9), round(s.t1 - lo, 9), s.rid,
              s.tid, s.cause] for s in data.spans if lo <= s.t0 < hi]
    tokens = sorted(round(t - lo, 9) for r in data.requests
                    for t in r.tokens if lo <= t < hi)
    with gzip.open(path, "wt") as f:
        json.dump({"window": [0.0, hi - lo], "model": spec.config["model"],
                   "device_kind": kind, "events": rows, "spans": spans,
                   "tokens": tokens}, f)
    return len(rows)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out")
    ap.add_argument("--slice-s", type=float, default=0.15)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from chipbench.run import use_cache
    use_cache()
    from chipbench import spec as spec_mod
    spec = spec_mod.load(args.workload, ROOT)
    with tempfile.TemporaryDirectory() as tdir:
        result, info = run_spanned(spec, args.seed, args.seconds,
                                   bool(args.trace), t_proc0=T0,
                                   trace_dir=tdir)
    if info["unread"]:
        print(f"[span_run] read nothing for {', '.join(info['unread'])}",
              file=sys.stderr)
    if args.out and args.trace:
        out = Path(args.out)
        out.mkdir(parents=True, exist_ok=True)
        n = write_slice(info["data"], spec, result["device"]["kind"],
                        out / "slice.json.gz", args.slice_s)
        print(f"[span_run] {n} events in {out / 'slice.json.gz'}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
