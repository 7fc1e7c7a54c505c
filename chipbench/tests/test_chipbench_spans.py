"""The readers of the program's host spans, token stamps and counters,
and of the named device programs: on hand-made runs, on a tiny traced CPU
run (the engine's spans on), and on a slice recorded on a TPU v5e."""
import time

import pytest

from chipbench import cell
from chipbench import trace as tr
from chipbench.spec import reader
from chipbench.tests.tiny import tiny_spec
from repro.core import Span

E = tr.Event
SPAN_READERS = ["itl_ms.p95", "decode_launch_ms.open",
                "decode_token_wait_ms.open", "sched_decision_us.open",
                "sched_lock_wait_us.open", "device_idle_launch.open"]
DEVICE_READERS = ["decode_step_device_ms.open", "programs_per_token.open"]


def _req(first, done, n_out, tokens=()):
    return cell.Req(first, first, first, done, 16, n_out, n_out, tokens)


def _run(requests=(), events=None, spans=None, tokens=0,
         window=(0.0, 1.0), counters=None):
    return cell.RunData(None, window[1] - window[0], window, 0.0,
                        list(requests), [], 0, tokens, events, None, spans,
                        counters)


def _spans():
    return [
        # two decode steps of request 1, and one past the window
        Span("decode.launch", 0.100, 0.102, rid=1),
        Span("decode.token_wait", 0.102, 0.112, rid=1),
        Span("decode.launch", 0.200, 0.206, rid=1),
        Span("decode.token_wait", 0.206, 0.222, rid=1),
        Span("decode.launch", 1.5, 1.6, rid=1),
        Span("prefill.launch", 0.300, 0.310, rid=2),
        # task 7: woken by task 3's commit, placed, committed
        Span("lock_wait", 0.090, 0.090010, tid=7),
        Span("wake", 0.090010, 0.090030, tid=7, cause=3),
        Span("lock_wait", 0.095, 0.095050, tid=7),
        Span("place", 0.095050, 0.095090, tid=7),
        Span("lock_wait", 0.113, 0.113002, tid=7),
        Span("commit", 0.113002, 0.113052, tid=7),
        # task 8: committed in the window, its wake before it
        Span("wake", -0.5, -0.49999, tid=8),
        Span("commit", 0.5, 0.50003, tid=8),
        # task 9: placed, never committed
        Span("place", 0.6, 0.7, tid=9),
    ]


def test_span_readers_on_a_small_run():
    run = _run(spans=_spans())
    assert reader("decode_launch_ms.open")(run) == pytest.approx(4.0)
    assert reader("decode_token_wait_ms.closed")(run) == pytest.approx(13.0)
    # (20 + 40 + 50 us for task 7, 10 + 30 us for task 8) over 2 tasks
    assert reader("sched_decision_us.open")(run) == pytest.approx(75.0)
    assert reader("sched_lock_wait_us.closed")(run) == pytest.approx(31.0)


def test_itl_reads_token_stamps():
    reqs = [_req(0.1, 0.4, 4, (0.1, 0.2, 0.3, 0.4)),
            _req(0.2, 0.3, 2, (0.2, 0.3))]
    assert reader("itl_ms.p95")(_run(reqs)) == pytest.approx(100.0)
    late = reqs + [_req(0.5, 0.0, 1, (0.5,))]   # never finished
    assert reader("itl_ms.p95")(_run(late)) == float("inf")


def test_device_program_readers():
    evs = [E("XLA Modules", "jit_serve_decode(123)", 0.10, 0.011, ""),
           E("XLA Modules", "jit_serve_decode(123)", 0.20, 0.013, ""),
           E("XLA Modules", "jit__argmax(9)", 0.212, 0.0001, ""),
           E("XLA Modules", "jit_serve_prefill(5)", 0.30, 0.030, ""),
           E("XLA Modules", "jit_serve_decode(123)", 1.20, 0.5, "")]
    run = _run(events=evs, tokens=2)
    assert reader("decode_step_device_ms.closed")(run) == \
        pytest.approx(12.0)
    assert reader("programs_per_token.open")(run) == pytest.approx(2.0)


def test_idle_in_launch():
    # device busy but for [0.1, 0.3] and [0.5, 1.0]; the first gap's
    # midpoint is inside a launch span, the second's is not
    evs = [E("XLA Ops", "fusion.1", 0.0, 0.1, ""),
           E("XLA Ops", "fusion.2", 0.3, 0.2, "")]
    spans = [Span("decode.launch", 0.15, 0.25, rid=1),
             Span("decode.token_wait", 0.25, 0.3, rid=1),
             Span("prefill.launch", 0.95, 0.99, rid=2)]
    run = _run(events=evs, spans=spans)
    assert reader("device_idle_launch.open")(run) == pytest.approx(20.0)


def test_decode_in_place_share_reads_the_counters():
    share = reader("decode_in_place_share.closed")
    counted = {"decode_steps": 40, "decode_in_place": 30, "n_cores": 4}
    assert share(_run(counters=counted)) == pytest.approx(0.75)
    assert share(_run(counters={"decode_steps": 0,
                                "decode_in_place": 0})) is None
    assert share(_run()) is None


@pytest.mark.parametrize("name", SPAN_READERS + DEVICE_READERS)
def test_nothing_to_read_gives_nothing(name):
    """A run without spans, stamps or named programs (an untraced run,
    or a program that makes none) reads nothing."""
    plain = _run([_req(0.1, 0.4, 4)], tokens=4, events=[
        E("XLA Ops", "fusion.1", 0.0, 0.1, "")])
    untraced = _run([_req(0.1, 0.4, 4)], spans=[], tokens=4)
    for run in (plain, untraced):
        assert reader(name)(run) is None


def test_traced_cpu_run_reports_span_metrics(tmp_path):
    spec = tiny_spec("dense", "open")
    result, info = cell.run(
        spec, 2**31 + 11, 2.0, True, t_proc0=time.perf_counter(),
        trace_dir=str(tmp_path), require_chip=False)
    assert result["correct"], result["checks"]
    got = result["metrics"]
    five = SPAN_READERS[:5]
    assert set(five) <= {m["name"] for m in spec.per_layer}
    assert set(five) <= set(got)
    assert all(got[n]["value"] > 0 for n in five)
    assert got["decode_in_place_share.open"]["value"] == 1.0
    data = info["data"]
    assert all(len(r.tokens) == r.n_out for r in data.requests)
    assert {s.name for s in data.spans} >= {
        "prefill.launch", "decode.token_wait", "wake", "place", "commit",
        "lock_wait"}


def test_recorded_v5e_slice_with_spans():
    """A slice recorded on a TPU v5e (``tools/span_run.py --out``, cell
    stablelm-3b.chat_poisson): the first 150 ms of a traced
    window, with the named programs, the spans and the token stamps."""
    import gzip
    import json
    from pathlib import Path
    path = Path(__file__).parent / "data" / "trace_slice_spans_v5e.json.gz"
    with gzip.open(path, "rt") as f:
        rec = json.load(f)
    evs = [E(ln, nm, t0, dur, "") for ln, nm, t0, dur in rec["events"]]
    spans = [Span(*row) for row in rec["spans"]]
    run = _run(events=evs, spans=spans, tokens=len(rec["tokens"]),
               window=tuple(rec["window"]))
    decode = [e for e in tr.modules(evs)
              if e.name.startswith("jit_serve_decode(")]
    assert len(decode) == 2
    step = reader("decode_step_device_ms.open")(run)
    assert step == pytest.approx(1e3 * sum(e.dur for e in decode) / 2)
    assert 10.0 < step < 13.0
    # two prefills and two decode steps, five programs each, two tokens
    assert reader("programs_per_token.open")(run) == pytest.approx(10.0)
    idle_launch = reader("device_idle_launch.open")(run)
    assert 0 < idle_launch < reader("device_idle.open")(run)
    assert reader("decode_launch_ms.open")(run) > 0
    # each decode step's wake names the prefill or decode task whose
    # commit made it ready
    commits = {s.tid for s in spans if s.name == "commit"}
    assert {s.cause for s in spans if s.name == "wake" and s.cause >= 0} \
        <= commits
