"""Runs a cell traced, as ``run.py --trace 1`` does (the engine's host
spans on), prints its result line, and writes ``slice.json.gz``: the
``XLA Ops`` and ``XLA Modules`` events, spans and token stamps from the
first ``jit_serve_prefill`` program to ``--slice-s`` past its start
(``chipbench/tests/data`` keeps one).

    python3 chipbench/tools/span_run.py --workload <cell> --seed <n> \\
        --seconds <s> --out <dir>
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


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
    ap.add_argument("--out", required=True)
    ap.add_argument("--slice-s", type=float, default=0.15)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from chipbench.run import use_cache
    use_cache()
    from chipbench import cell, spec as spec_mod
    spec = spec_mod.load(args.workload, ROOT)
    with tempfile.TemporaryDirectory() as tdir:
        result, info = cell.run(spec, args.seed, args.seconds, True,
                                t_proc0=T0, trace_dir=tdir)
    if info["unread"]:
        print(f"[span_run] read nothing for {', '.join(info['unread'])}",
              file=sys.stderr)
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
