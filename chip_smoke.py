"""Smoke run of the serving path on one TPU chip.

    python chip_smoke.py

Serves stablelm-3b at its published widths in bfloat16 (random weights
from seed 0) through ``ServingEngine`` -> ``SchedulingKernel`` ->
``ThreadedRuntime`` -> jitted ``prefill``/``decode_step`` -> Pallas kernels,
the engine ``python -m repro.launch.serve --full-config`` builds.  It warms
prefill and decode, serves 8 requests of 256 prompt tokens and 16 new
tokens each, and then checks on the chip:

* the compiled prefill at the served length holds a Pallas kernel
  (``tpu_custom_call``), so no shape guard fell back to XLA unseen;
* no payload raised, and every request produced all its tokens;
* ``flash_attention_pallas`` agrees with ``kernels.ref.attention_ref`` at
  the served attention shape;
* one request's prefill-then-cached-decode logits agree with
  ``models.forward`` over the same tokens.

The lines before the last are bring-up facts, not benchmark numbers.  The
last line is ``{"ok": true, "device": {...}}`` only when every check
passed; otherwise the script exits non-zero without printing it.  It
refuses to run without a TPU and while ``REPRO_FORCE_PALLAS_INTERPRET`` is
set.  Everything runs in this one process: the chip belongs to it.
"""
from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

ARCH = "stablelm-3b"
N_REQUESTS = 8
PROMPT_LEN = 256        # a multiple of 128: prefill attention takes Pallas
NEW_TOKENS = 16
MAX_LEN = 512
SEED = 0

# Served bf16 logits against models.forward in float32 activations over
# the same bf16 weights.  bf16 rounds each of the 64 residual updates to
# a relative 2**-9 (about 2e-3); as a random walk that is about 1.6e-2 of
# the logits' norm after 32 layers.  5e-2 leaves 3x room.  A cache written
# at the wrong position, a wrong rotary offset or weights held in fp8
# (relative step 6e-2 per rounding) land far above it.
LOGITS_REL_L2_TOL = 5e-2
# Flash kernel against the XLA reference: both round their output to
# bf16 (relative step 2**-8, about 3.9e-3) after f32 accumulation, so they
# may differ by a step or two; a wrong mask or scale errs by 1e-1 or more.
FLASH_TOL = 1e-2


class SmokeFailure(Exception):
    pass


def fact(name: str, value) -> None:
    print(f"[chip_smoke] {name}: {value}", flush=True)


class CompileLog:
    """Counts compilations and sums their seconds (trace, lowering and
    backend compile or persistent-cache read) from JAX's monitoring
    events."""

    _PHASES = ("/jax/core/compile/jaxpr_trace_duration",
               "/jax/core/compile/jaxpr_to_mlir_module_duration",
               "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        from jax import monitoring
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, name: str, secs: float, **_) -> None:
        if name in self._PHASES:
            self.seconds += secs
        if name == self._PHASES[-1]:
            self.compiles += 1

    def _on_event(self, name: str, **_) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self) -> tuple[float, int, int]:
        return self.seconds, self.compiles, self.cache_hits

    def since(self, mark: tuple[float, int, int]) -> str:
        s, c, h = mark
        return (f"{self.seconds - s:.3f} s over {self.compiles - c} "
                f"compiles ({self.cache_hits - h} from the persistent cache)")


def check_device() -> dict:
    if "REPRO_FORCE_PALLAS_INTERPRET" in os.environ:
        raise SmokeFailure("REPRO_FORCE_PALLAS_INTERPRET is set: it forces "
                           "the Pallas interpreter; unset it")
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SmokeFailure(f"no TPU found: JAX's first device is "
                           f"{dev.platform!r} ({dev.device_kind})")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def check_prefill_has_kernel(engine) -> None:
    import jax.numpy as jnp
    tokens = jnp.zeros((1, PROMPT_LEN), jnp.int32)
    hlo = engine._prefill.lower(engine.params, tokens).compile().as_text()
    if "tpu_custom_call" not in hlo:
        raise SmokeFailure("compiled prefill holds no tpu_custom_call: the "
                           "Pallas attention fell back to XLA")
    fact("prefill kernel", "compiled prefill holds tpu_custom_call")


def check_flash(cfg) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.flash_attention import flash_attention_pallas
    from repro.kernels.ref import attention_ref

    shape = (1, cfg.n_heads, PROMPT_LEN, cfg.resolved_head_dim)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(SEED + 1), 3)
    q, k, v = (jax.random.normal(key, shape, jnp.bfloat16)
               for key in (kq, kk, kv))
    got = flash_attention_pallas(q, k, v, causal=True)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda q, k, v: attention_ref(q, k, v, causal=True))(
            q, k, v)
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    err = np.abs(got - want)
    bound = FLASH_TOL + FLASH_TOL * np.abs(want)
    fact("flash vs attention_ref", f"shape {list(shape)} bf16, max abs err "
         f"{err.max():.3e}, worst err/bound {(err / bound).max():.3f}")
    if not (err <= bound).all():
        raise SmokeFailure(f"flash_attention_pallas differs from "
                           f"attention_ref by {err.max():.3e}")


def reference_logits(params, cfg, tokens):
    """``models.forward`` computing in float32 over the served weights:
    the embedding, final norm and head are upcast, so every layer's
    activations and matmuls are f32 (the bf16 layer weights promote)."""
    import jax
    import jax.numpy as jnp
    from repro.models import forward

    def f(p, t):
        p32 = {name: (leaf if name == "stacks" else
                      jax.tree.map(lambda a: a.astype(jnp.float32), leaf))
               for name, leaf in p.items()}
        with jax.default_matmul_precision("highest"):
            return forward(p32, cfg, t)[0]

    return jax.jit(f)(params, tokens)


def check_logits(engine, cfg, req) -> None:
    """Replays ``req`` through the engine's own compiled prefill and decode
    (its argmaxes must be the tokens the engine served) and compares those
    logits with the reference forward over the same tokens."""
    import jax.numpy as jnp
    import numpy as np

    tokens = np.concatenate([req.prompt, req.out_tokens[:-1]]).astype(np.int32)
    logits, state = engine._prefill(engine.params,
                                    jnp.asarray(req.prompt)[None, :])
    rows = [logits[0]]
    for tok in req.out_tokens[:-1]:
        logits, state = engine._decode(engine.params, state,
                                       jnp.asarray([tok], jnp.int32))
        rows.append(logits[0])
    served = np.asarray(jnp.stack(rows), np.float32)      # [NEW_TOKENS, V]
    replayed = served.argmax(axis=-1).tolist()
    if replayed != list(req.out_tokens):
        raise SmokeFailure(f"replay of request {req.rid} gives tokens "
                           f"{replayed}, the engine served {req.out_tokens}")
    ref = reference_logits(engine.params, cfg, jnp.asarray(tokens)[None, :])
    ref = np.asarray(ref[0, PROMPT_LEN - 1:], np.float32)  # [NEW_TOKENS, V]
    rel = (np.linalg.norm(served - ref, axis=-1)
           / np.linalg.norm(ref, axis=-1))
    top1 = float((served.argmax(-1) == ref.argmax(-1)).mean())
    fact("cached decode vs forward", f"{len(rel)} positions, relative L2 "
         f"max {rel.max():.3e} mean {rel.mean():.3e}, max abs "
         f"{np.abs(served - ref).max():.3e}, top-1 agreement {top1:.3f}")
    if not rel.max() <= LOGITS_REL_L2_TOL:
        raise SmokeFailure(f"served logits differ from models.forward: "
                           f"relative L2 {rel.max():.3e} > "
                           f"{LOGITS_REL_L2_TOL:.0e}")


def run() -> dict:
    device = check_device()
    fact("device", device)

    import jax
    import numpy as np
    from repro.core import tpu_pod_slices
    from repro.launch.cache import use_compile_cache
    from repro.launch.serve import load_config, run_failures
    from repro.serve import ServingEngine

    fact("compile cache", use_compile_cache())
    log = CompileLog()
    cfg = load_config(ARCH, full_config=True)

    mark = log.mark()
    t0 = time.perf_counter()
    engine = ServingEngine(cfg, tpu_pod_slices(2, 2), scheduler="DAM-P",
                           max_len=MAX_LEN, seed=SEED)
    jax.block_until_ready(engine.params)
    n_bytes = sum(a.nbytes for a in jax.tree.leaves(engine.params))
    fact("model", f"{cfg.name} {cfg.dtype}: {cfg.n_layers} layers, d_model "
         f"{cfg.d_model}, {cfg.n_heads}x{cfg.resolved_head_dim} heads, d_ff "
         f"{cfg.d_ff}, vocab {cfg.vocab}; {n_bytes} parameter bytes")
    fact("params init", f"{time.perf_counter() - t0:.3f} s wall; compile "
         f"{log.since(mark)}")

    mark = log.mark()
    t0 = time.perf_counter()
    engine.warmup(PROMPT_LEN)
    fact("warm-up (cold phase)", f"{time.perf_counter() - t0:.3f} s wall; "
         f"compile {log.since(mark)}")

    rng = np.random.default_rng(SEED)
    reqs = [engine.submit(rng.integers(0, cfg.vocab, size=PROMPT_LEN),
                          max_new_tokens=NEW_TOKENS)
            for _ in range(N_REQUESTS)]
    mark = log.mark()
    t0 = time.perf_counter()
    metrics = engine.run(timeout=600.0)
    fact("served (warm phase)", f"{time.perf_counter() - t0:.3f} s wall; "
         f"compile {log.since(mark)}")
    done = sum(len(r.out_tokens) == r.max_new_tokens for r in reqs)
    fact("requests completed", f"{done} of {len(reqs)}")
    fact("tokens produced", sum(len(r.out_tokens) for r in reqs))
    problems = run_failures(engine, metrics)
    if problems:
        raise SmokeFailure("; ".join(problems))
    fact("payload errors", "none")

    mark = log.mark()
    check_prefill_has_kernel(engine)
    check_flash(cfg)
    check_logits(engine, cfg, reqs[0])
    fact("checks", f"all passed; compile {log.since(mark)}")
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        fact("peak device bytes", stats["peak_bytes_in_use"])
    return device


def main() -> int:
    src = Path(__file__).resolve().parent / "src"
    try:
        if not (src / "repro").is_dir():
            raise SmokeFailure(f"the repository's package is not beside "
                               f"this script: no {src / 'repro'}")
        sys.path.insert(0, str(src))
        device = run()
    except SmokeFailure as e:
        print(f"[chip_smoke] FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
