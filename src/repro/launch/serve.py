"""Serving driver: requests through the PTT-scheduled engine, with
criticality-aware placement under injected interference.

    PYTHONPATH=src python -m repro.launch.serve --arch xlstm-125m \
        --requests 12 --scheduler DAM-P --slow-core 0:4

By default it serves the reduced config, small enough for a CPU.
``--full-config`` serves the architecture at its published widths in
bfloat16, which needs an accelerator (``chip_smoke.py`` drives that path on
one TPU).  The exit code is non-zero when a payload raised or a request did
not produce every token it asked for.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
from typing import Optional, Sequence

import numpy as np

from ..configs import ARCHS
from ..configs.base import ModelConfig
from ..core import tpu_pod_slices
from ..serve import ServingEngine
from .cache import use_compile_cache


def load_config(arch: str, full_config: bool) -> ModelConfig:
    """The reduced config, or the published one served in bfloat16."""
    if full_config:
        return dataclasses.replace(ARCHS[arch], dtype="bfloat16")
    return ARCHS[arch].reduced()


def run_failures(engine: ServingEngine, metrics) -> list[str]:
    """Why a finished run must not report success: payload exceptions the
    runtime caught (it keeps running so barrier partners never hang), and
    requests that did not produce every token they asked for."""
    problems = list(metrics.errors)
    short = [r.rid for r in engine.requests.values()
             if len(r.out_tokens) != r.max_new_tokens]
    if short:
        problems.append(f"{len(short)} of {len(engine.requests)} requests "
                        f"did not finish: rids {short}")
    return problems


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="xlstm-125m")
    ap.add_argument("--full-config", action="store_true",
                    help="serve the published (not reduced) config in bf16")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=4)
    ap.add_argument("--scheduler", default="DAM-P")
    ap.add_argument("--pods", type=int, default=2)
    ap.add_argument("--slices", type=int, default=2)
    ap.add_argument("--slow-core", default=None,
                    help="core:factor, e.g. 0:4 = core 0 runs 4x slower")
    args = ap.parse_args(argv)

    use_compile_cache()
    cfg = load_config(args.arch, args.full_config)
    topo = tpu_pod_slices(args.pods, args.slices)
    slowdown = None
    if args.slow_core:
        c, f = args.slow_core.split(":")
        slowdown = {int(c): float(f)}
    engine = ServingEngine(cfg, topo, scheduler=args.scheduler,
                           max_len=args.prompt_len + args.new_tokens + 8,
                           slowdown=slowdown)
    rng = np.random.default_rng(0)
    for _ in range(args.requests):
        engine.submit(rng.integers(0, cfg.vocab, size=args.prompt_len),
                      max_new_tokens=args.new_tokens)
    metrics = engine.run(timeout=300.0)
    stats = engine.latency_stats()
    print(f"[serve] {stats}")
    print(f"[serve] prefill placement: "
          f"{ {k: v for k, v in metrics.priority_placement().items()} }")
    problems = run_failures(engine, metrics)
    for p in problems:
        print(f"[serve] FAILED: {p}", file=sys.stderr)
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
