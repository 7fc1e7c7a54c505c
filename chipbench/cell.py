"""One run of one cell: set-up, the measured window, the check that
decides ``correct``, and the result line.

Set-up makes the engine (its weights on the device, in one jitted call
from the seed), warms every prompt length the run will send with one
prefill and one decode, then serves one short request of each length
through ``ServingEngine.submit`` so that every task type is known to the
scheduler.  ``setup_s`` runs from the process's start to the window's
first instant.

The window (in a traced run, its first ``TRACE_S`` seconds only) drives
``ServingEngine.submit``: an open loop sends each
request at its due time from this thread, a closed loop keeps each
client's one request in flight.  After the window every request due in it
is awaited, up to a minute past the close; one that never completes has
failed.  Then the device's peak memory is read, the program is freed, and
the plain reference runs over a seeded sample of finished requests.

A traced run builds the engine with its host spans on and carries them
(``RunData.spans``); an untraced run keeps them off, so that the
end-to-end metrics are read with nothing added to the serving path.  Every
run carries each request's token stamps and the engine's counters.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import os
import time
from typing import NamedTuple, Optional

import numpy as np

from . import generator
from .spec import Spec, reader, reference

AWAIT_S = 60.0         # a due request may finish this long past the close
POLL_S = 1e-3          # closed-loop clients look for their reply this often
# a traced run's window: long enough for some tens of requests, short
# enough that reading its trace stays a small part of the run
TRACE_S = 20.0


class NoChip(Exception):
    """No accelerator, or fewer chips than the cell asks for."""


class CompileLog:
    """Counts compilations (backend compiles or persistent-cache reads)
    from JAX's monitoring events."""

    def __init__(self):
        from jax import monitoring
        self.compiles = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, name: str, secs: float, **_) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1


class Req(NamedTuple):
    """One request of the window, on the ``perf_counter`` clock."""
    due: float
    submit: float
    first: float          # first token out (prefill commit); 0 = never
    done: float           # last token out; 0 = never
    prompt_len: int
    n_out: int
    max_new: int
    tokens: tuple = ()    # each token's stamp (``Request.t_tokens``)

    @property
    def finished(self) -> bool:
        return self.done > 0 and self.n_out == self.max_new


class Task(NamedTuple):
    """A ``TaskRecord`` moved onto the ``perf_counter`` clock."""
    type_name: str
    t_ready: float
    t_start: float
    t_end: float


@dataclasses.dataclass
class RunData:
    """What a metric reader gets."""
    spec: Spec
    seconds: float
    window: tuple[float, float]
    setup_s: float
    requests: list[Req]
    tasks: list[Task]
    compiles_in_window: int
    tokens_in_window: int
    events: Optional[list]            # trace.Event list of a traced run
    peak: Optional[dict]              # peaks.json entry of the device
    # the engine's host spans (``RunMetrics.spans``) of a traced run
    spans: Optional[list] = None
    # the engine's counters over the whole run, set-up included: every
    # numeric field of its drained ``RunMetrics`` (``decode_steps``,
    # ``decode_in_place``, ...)
    counters: Optional[dict] = None


def check_device(chips: int) -> dict:
    if "REPRO_FORCE_PALLAS_INTERPRET" in os.environ:
        raise NoChip("REPRO_FORCE_PALLAS_INTERPRET is set: it forces the "
                     "Pallas interpreter")
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"{len(devs)} chips, the cell asks for {chips}")
    return device_info()


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def model_seed(seed: int) -> int:
    """A weight seed that ``PRNGKey`` holds whole (32 signed bits)."""
    return int(generator.rng_for(seed, "weights").integers(0, 2**31 - 1))


def _sleep_until(t: float) -> None:
    d = t - time.perf_counter()
    if d > 0:
        time.sleep(d)


class _Sent:
    __slots__ = ("due", "submit", "req", "prompt")

    def __init__(self, due, submit, req, prompt):
        self.due, self.submit, self.req, self.prompt = due, submit, req, prompt


def _build(spec: Spec, seed: int, spans: bool):
    import jax
    from repro.configs.base import ModelConfig
    from repro.core import tpu_pod_slices
    from repro.serve import ServingEngine
    serving = spec.config["serving"]
    cfg = ModelConfig(**spec.config["model"])
    engine = ServingEngine(
        cfg, tpu_pod_slices(serving["pods"], serving["slices_per_pod"]),
        scheduler=serving["scheduler"], max_len=serving["max_len"],
        seed=model_seed(seed), spans=spans)
    jax.block_until_ready(engine.params)
    return engine


def _work(spec: Spec, seed: int, seconds: float):
    """The run's requests: an open loop's schedule, or each closed-loop
    client's queue, with their prompt tokens."""
    vocab = spec.config["model"]["vocab"]
    mix = spec.traffic
    if mix["loop"] == "open":
        items = generator.open_schedule(mix, spec.params["rate_rps"],
                                        seconds, seed)
        return items, generator.prompt_tokens(items, vocab, seed)
    queues = generator.closed_queues(mix, spec.params["clients"],
                                     spec.params["requests_per_client"],
                                     seed)
    flat = [it for q in queues for it in q]
    toks = generator.prompt_tokens(flat, vocab, seed)
    it = iter(toks)
    return queues, [[next(it) for _ in q] for q in queues]


def _warm(engine, lengths: list[int], vocab: int) -> None:
    for n in lengths:
        engine.warmup(n)
    engine.runtime.start()
    reqs = [engine.submit(np.arange(n, dtype=np.int32) % vocab,
                          max_new_tokens=2) for n in lengths]
    deadline = time.perf_counter() + 600
    while any(r.t_done == 0 for r in reqs):
        if engine.runtime.metrics.errors or time.perf_counter() > deadline:
            raise RuntimeError(f"warm-up failed: "
                               f"{engine.runtime.metrics.errors}")
        time.sleep(0.01)


def _open_loop(engine, items, prompts, t0: float, seconds: float):
    sent = []
    for it, p in zip(items, prompts):
        due = t0 + it.due_s
        _sleep_until(due)
        sub = time.perf_counter()
        sent.append(_Sent(due, sub, engine.submit(
            p, max_new_tokens=it.out_len), p))
    _sleep_until(t0 + seconds)
    return sent


def _closed_loop(engine, queues, prompts, t0: float, seconds: float):
    sent, current = [], []
    nxt = [0] * len(queues)

    def send(c):
        if nxt[c] >= len(queues[c]):
            raise RuntimeError(f"closed-loop client {c} ran out of requests "
                               f"({len(queues[c])}); raise "
                               f"requests_per_client")
        it, p = queues[c][nxt[c]], prompts[c][nxt[c]]
        nxt[c] += 1
        now = time.perf_counter()
        s = _Sent(now, now, engine.submit(p, max_new_tokens=it.out_len), p)
        sent.append(s)
        return s

    _sleep_until(t0)
    current = [send(c) for c in range(len(queues))]
    t1 = t0 + seconds
    while time.perf_counter() < t1:
        for c, s in enumerate(current):
            if s.req.t_done > 0:
                current[c] = send(c)
        time.sleep(POLL_S)
    return sent


def _await(engine, sent, deadline: float) -> None:
    while any(s.req.t_done == 0 for s in sent):
        if engine.runtime.metrics.errors or time.perf_counter() > deadline:
            return
        time.sleep(0.005)


def _sample(reqs: list[Req], n: int, seed: int) -> list[int]:
    """Indices of a seeded sample of finished requests: the one with the
    most served tokens, then others drawn at random."""
    done = [i for i, r in enumerate(reqs) if r.finished]
    if not done:
        return []
    longest = max(done, key=lambda i: (reqs[i].n_out, reqs[i].prompt_len))
    rest = [i for i in done if i != longest]
    rng = generator.rng_for(seed, "sample")
    pick = rng.permutation(len(rest))[:max(n - 1, 0)]
    return [longest] + [rest[i] for i in sorted(pick)]


def gap_rows(spec: Spec, rows: list[tuple[np.ndarray, list]],
             n_rows: int):
    """Token rows (prompt, then served tokens but the last), the served
    token each position predicts, and which positions predict one; padded
    to the served ``max_len`` and to ``n_rows`` rows, so that the
    reference compiles one shape."""
    length = spec.config["serving"]["max_len"]
    tokens = np.zeros((n_rows, length), np.int32)
    targets = np.zeros((n_rows, length), np.int32)
    mask = np.zeros((n_rows, length), bool)
    for r, (prompt, out) in enumerate(rows):
        seq = np.concatenate([prompt, np.asarray(out[:-1], np.int32)])
        tokens[r, :len(seq)] = seq
        p = len(prompt)
        targets[r, p - 1:p - 1 + len(out)] = out
        mask[r, p - 1:p - 1 + len(out)] = True
    return tokens, targets, mask


def reference_gaps(spec: Spec, seed: int, tokens, targets,
                   quant: Optional[str] = None):
    """The reference's logit gaps [k, n, L] of each target set [k, n, L],
    and its own best tokens [n, L]; ``quant`` runs the control."""
    from .reference import common
    m = common.Model.from_dict(spec.config["model"])
    ms = model_seed(seed)
    return common.run_layers(reference(spec.config["reference"]).plan(m, ms),
                             m, ms, tokens, targets, quant=quant)


def logit_gaps(spec: Spec, seed: int, rows, n_rows: int,
               control: Optional[str] = None):
    """The widest gap, over every served token of ``rows``, by which the
    reference's logit of the served token lies below its best logit; with
    ``control``, the widest gap of the tokens that the control (the
    reference with its weights in that precision) puts first at the same
    positions, else None; and how many positions were compared."""
    tokens, targets, mask = gap_rows(spec, rows, n_rows)
    if not mask.any():
        return math.inf, (math.inf if control else None), 0
    sets = targets[None]
    if control:
        _, ctop = reference_gaps(spec, seed, tokens, sets, quant=control)
        sets = np.stack([targets, ctop])
    gaps, _ = reference_gaps(spec, seed, tokens, sets)
    widest = [float(g[mask].max()) for g in gaps]
    return widest[0], (widest[1] if control else None), int(mask.sum())


def judge(chk: dict, failed: int, errors: int, gap: float,
          n_tok: int) -> tuple[bool, dict]:
    """``correct``, and each number compared beside its limit."""
    checks = {
        "failed_requests": {"value": failed, "limit": 0},
        "payload_errors": {"value": errors, "limit": 0},
        "logit_gap": {"value": gap, "limit": chk["logit_gap"]},
        "tokens_compared": {"value": n_tok,
                            "limit": chk["min_tokens_compared"]},
    }
    correct = (failed == 0 and errors == 0
               and n_tok >= max(chk["min_tokens_compared"], 1)
               and gap <= chk["logit_gap"])
    return bool(correct), checks


def run(spec: Spec, seed: int, seconds: float, trace: bool, *,
        t_proc0: float, trace_dir: Optional[str] = None,
        require_chip: bool = True,
        control: Optional[str] = None) -> tuple[dict, dict]:
    """Runs the cell; returns the result line and what lies behind it.
    With ``control`` (``"int8"`` or ``"fp8"``) the control stands in the
    program's place for the comparison: the check judges the tokens that
    the reference puts first with its weights in that precision, at the
    positions of the served tokens.  A traced run's window is at most
    ``TRACE_S`` long."""
    device = check_device(spec.chips) if require_chip else device_info()
    if trace:
        seconds = min(seconds, TRACE_S)
    import jax
    log = CompileLog()
    engine = _build(spec, seed, spans=trace)
    work, prompts = _work(spec, seed, seconds)
    closed = spec.traffic["loop"] == "closed"
    flat = [it for q in work for it in q] if closed else work
    _warm(engine, sorted({it.prompt_len for it in flat}),
          spec.config["model"]["vocab"])

    capture = None
    if trace:
        from .trace import Capture
        capture = Capture(trace_dir)
    t0 = time.perf_counter()
    if capture is not None:
        t0 = capture.mark()
    setup_s = t0 - t_proc0
    c0 = log.compiles
    drive = _closed_loop if closed else _open_loop
    sent = drive(engine, work, prompts, t0, seconds)
    t1 = time.perf_counter()
    tokens_in_window = sum(len(s.req.out_tokens) for s in sent)
    compiles = log.compiles - c0
    if capture is not None:
        capture.stop()
    _await(engine, sent, t1 + AWAIT_S)
    metrics = engine.runtime.drain(timeout=30.0)
    errors = list(metrics.errors)

    stats_mem = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(stats_mem.get("peak_bytes_in_use", 0))
    rt0 = engine.runtime.t0
    tasks = [Task(r.type_name, r.t_ready + rt0, r.t_start + rt0,
                  r.t_end + rt0) for r in metrics.records]
    reqs = [Req(s.due, s.submit, s.req.t_first_token, s.req.t_done,
                len(s.prompt), len(s.req.out_tokens), s.req.max_new_tokens,
                tuple(s.req.t_tokens))
            for s in sent]
    outs = [(s.prompt, list(s.req.out_tokens)) for s in sent]
    spans = None if metrics.spans is None else list(metrics.spans)
    counters = counters_of(metrics)
    del engine, metrics, sent
    gc.collect()

    events = peak = None
    if capture is not None:
        from . import counts
        from .trace import load
        events = load(trace_dir, capture.marker_ns)
        peak = counts.peaks(device["kind"]) if require_chip else None
    data = RunData(spec, seconds, (t0, t1), setup_s, reqs, tasks, compiles,
                   tokens_in_window, events, peak, spans, counters)
    chosen = spec.per_layer if trace else spec.end_to_end
    out_metrics, unread = {}, []
    for m in chosen:
        v = reader(m["name"])(data)
        if v is not None and math.isfinite(v):
            out_metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        else:
            unread.append(m["name"])
    if trace:
        from . import trace as tr
        device["busy_s"] = tr.busy_seconds(tr.ops(events), t0, t1)
        device["window_s"] = t1 - t0
    failed = sum(not r.finished for r in reqs)

    chk = spec.params["check"]
    pick = _sample(reqs, chk["sample_requests"], seed)
    program_gap, control_gap, n_tok = logit_gaps(
        spec, seed, [outs[i] for i in pick], chk["sample_requests"],
        control)
    correct, checks = judge(
        chk, failed, len(errors),
        program_gap if control is None else control_gap, n_tok)
    result = {"correct": correct, "attempted": len(reqs),
              "failed": failed, "metrics": out_metrics, "device": device}
    if trace:
        result["breakdown"] = breakdown(data)
    result["checks"] = checks
    info = {"errors": errors[:5], "sample_rows": len(pick),
            "program_gap": program_gap, "unread": unread,
            "p95_samples": len(reqs), "setup_s": setup_s,
            "latency": latency(data),
            "rows": [outs[i] for i in pick], "data": data}
    return result, info


def counters_of(metrics) -> dict:
    """Every numeric field of a ``RunMetrics``, by name."""
    out = {}
    for f in dataclasses.fields(metrics):
        v = getattr(metrics, f.name)
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            out[f.name] = v
    return out


def latency(data: RunData) -> dict:
    """Percentiles and means of the window's first-token and per-token
    times, in ms, for the run's log; empty for a closed loop."""
    if data.spec.traffic["loop"] == "closed":
        return {}
    from .metrics import common
    from .stats import mean
    out = {}
    for name, v in (("ttft", common.ttft_s(data)),
                    ("tpot", common.tpot_s(data))):
        for q in (50, 90, 95):
            out[f"{name}_p{q}"] = common.pct_ms(v, q)
        out[f"{name}_mean"] = 1e3 * mean(v)
    return out


def breakdown(data: RunData) -> dict:
    """The device operations that took most time, and device idle by what
    the host was doing."""
    from . import trace as tr
    lo, hi = data.window
    ops = tr.ops(data.events)
    idle = tr.gaps(ops, lo, hi)
    host = {
        "host_in_prefill_task": [(t.t_start, t.t_end) for t in data.tasks
                                 if t.type_name.startswith("prefill")],
        "host_in_decode_task": [(t.t_start, t.t_end) for t in data.tasks
                                if t.type_name.startswith("decode")],
        "task_ready_not_started": [(t.t_ready, t.t_start)
                                   for t in data.tasks],
        "request_between_tasks": [(r.submit, r.done if r.done else hi)
                                  for r in data.requests],
    }
    idle_gaps = tr.idle_by_host(idle, host)
    for row in idle_gaps:
        if row[0] == "other":
            row[0] = "no_request_in_flight"
    return {"device_ops": tr.top_ops(ops), "idle_gaps": idle_gaps}
