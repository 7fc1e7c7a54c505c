"""Host spans and per-token stamps of the serving path: off by default
and then free of side effects on what is served; on, one launch and one
token wait per model step inside its task, scheduler spans tied to the
tasks they decide on, and one stamp per token that leaves the engine."""
import collections
import random
import time

import numpy as np
import pytest

from repro.configs import ARCHS
from repro.core import BatchingConfig, tpu_pod_slices
from repro.serve import ServingEngine

PROMPT, NEW = 16, 4


@pytest.fixture(scope="module")
def cfg():
    return ARCHS["stablelm-3b"].reduced()


def _prompts(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, PROMPT) for _ in range(n)]


def _served(cfg, spans, n=4):
    eng = ServingEngine(cfg, tpu_pod_slices(2, 2), scheduler="DAM-P",
                        max_len=32, spans=spans)
    reqs = [eng.submit(p, max_new_tokens=NEW) for p in _prompts(cfg, n)]
    m = eng.run(timeout=300)
    assert not m.errors
    return eng, reqs, m


@pytest.fixture(scope="module")
def spanned(cfg):
    return _served(cfg, spans=True)


def test_spans_off_by_default_and_same_tokens(cfg, spanned):
    eng, reqs, m = _served(cfg, spans=False)
    assert m.spans is None
    assert ServingEngine(None, tpu_pod_slices(2, 2)).runtime.metrics \
        .spans is None
    _, on_reqs, on_m = spanned
    assert on_m.spans
    assert [r.out_tokens for r in reqs] == [r.out_tokens for r in on_reqs]


def test_token_stamps(spanned):
    _, reqs, _ = spanned
    for r in reqs:
        assert len(r.t_tokens) == len(r.out_tokens) == NEW
        assert all(a < b for a, b in zip(r.t_tokens, r.t_tokens[1:]))
        assert r.t_tokens[0] == r.t_first_token
        assert r.t_tokens[-1] == r.t_done


def test_step_spans_per_request(spanned):
    _, reqs, m = spanned
    by = collections.Counter((s.name, s.rid) for s in m.spans)
    for r in reqs:
        assert by["prefill.launch", r.rid] == 1
        assert by["prefill.token_wait", r.rid] == 1
        assert by["decode.launch", r.rid] == NEW - 1
        assert by["decode.token_wait", r.rid] == NEW - 1
    for s in m.spans:
        assert s.t0 <= s.t1
    # each token wait starts where its step's launch ended
    ends = {(s.rid, s.t1) for s in m.spans if s.name.endswith(".launch")}
    assert all((s.rid, s.t0) in ends for s in m.spans
               if s.name.endswith(".token_wait"))


def test_wake_causes_and_scheduler_spans(spanned):
    _, reqs, m = spanned
    names = collections.Counter(s.name for s in m.spans)
    tasks = m.n_tasks
    assert names["commit"] == names["place"] == names["wake"] == tasks
    # every decision waited for the lock once: wake, place and commit
    assert names["lock_wait"] == 3 * tasks
    committed = {s.tid for s in m.spans if s.name == "commit"}
    wakes = [s for s in m.spans if s.name == "wake"]
    roots = [w for w in wakes if w.cause == -1]
    assert len(roots) == len(reqs)             # one per submitted request
    for w in wakes:
        assert w.cause == -1 or w.cause in committed
        assert w.tid in committed
    first = min(wakes, key=lambda w: w.t0)
    assert first.cause == -1


def test_step_spans_inside_their_task(cfg):
    """One request in flight at a time: each decode task's record holds
    exactly one launch and one token wait, of that request."""
    eng = ServingEngine(cfg, tpu_pod_slices(2, 2), scheduler="DAM-P",
                        max_len=32, spans=True)
    eng.runtime.start()
    reqs = []
    for p in _prompts(cfg, 3, seed=1):
        reqs.append(eng.submit(p, max_new_tokens=NEW))
        deadline = time.monotonic() + 120
        while reqs[-1].t_done == 0 and time.monotonic() < deadline:
            time.sleep(1e-3)
    m = eng.runtime.drain(timeout=60)
    assert not m.errors
    t0 = eng.runtime.t0
    decode = [r for r in m.records if r.type_name == "decode"]
    assert len(decode) == 3 * (NEW - 1)
    for rec in decode:
        lo, hi = rec.t_start + t0, rec.t_end + t0
        owner = [r for r in reqs
                 if r.t_first_token < lo and hi <= r.t_done + 1e-9]
        assert len(owner) == 1
        inside = [s for s in m.spans if s.name.startswith("decode.")
                  and lo <= s.t0 and s.t1 <= hi]
        assert sorted(s.name for s in inside) == ["decode.launch",
                                                  "decode.token_wait"]
        assert {s.rid for s in inside} == {owner[0].rid}


@pytest.mark.parametrize("batching", [None, BatchingConfig(
    max_batch=4, delay_s=1e-3, member_cost=0.02)])
def test_shed_steps_get_no_stamp(batching):
    """Every served token, and only a served one, has its stamp, in the
    unbatched and the batched path: odd requests are shed at their
    second decode step."""
    eng = ServingEngine(None, tpu_pod_slices(2, 2), batching=batching,
                        prefill_s=1e-3, decode_s=1e-3)
    checks = collections.Counter()
    real = eng._shed_check

    def shed_odd(req):
        checks[req.rid] += 1
        if req.rid % 2 and checks[req.rid] == 2:
            req.shed, req.shed_cause = True, "deadline"
        return real(req)

    eng._shed_check = shed_odd
    reqs = [eng.submit(np.zeros(8, np.int32), max_new_tokens=NEW)
            for _ in range(6)]
    assert not eng.run(timeout=60).errors
    for r in reqs:
        assert r.shed == bool(r.rid % 2)
        assert len(r.t_tokens) == len(r.out_tokens) == (2 if r.shed
                                                         else NEW)
        assert r.t_tokens[0] == r.t_first_token
        assert r.t_tokens[-1] <= r.t_done
        if not r.shed:
            assert r.t_tokens[-1] == r.t_done


def test_open_loop_sends_at_due_times():
    """``t_submit`` is each request's due time: the start plus the seeded
    gaps summed, however late the sender ran."""
    eng = ServingEngine(None, tpu_pod_slices(2, 2), prefill_s=1e-3,
                        decode_s=1e-3)
    eng.run_open_loop([np.zeros(8, np.int32)] * 6, rate_rps=50.0,
                      max_new_tokens=2, arrival_seed=3, timeout=60)
    reqs = sorted(eng.requests.values(), key=lambda r: r.rid)
    arrivals = random.Random("serve-arrival:3")
    want = [arrivals.expovariate(50.0) for _ in reqs[1:]]
    np.testing.assert_allclose(np.diff([r.t_submit for r in reqs]), want,
                               rtol=0, atol=1e-9)


def test_spans_from_many_workers_are_all_kept():
    """Sixteen workers on a short switch interval append to one span
    list: one wake, place and commit, and three lock waits, per task."""
    import sys
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        eng = ServingEngine(None, tpu_pod_slices(2, 8), scheduler="DAM-C",
                            prefill_s=1e-4, decode_s=1e-4, spans=True)
        reqs = [eng.submit(np.zeros(8, np.int32), max_new_tokens=NEW)
                for _ in range(40)]
        m = eng.run(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not m.errors and all(r.t_done > 0 for r in reqs)
    names = collections.Counter(s.name for s in m.spans)
    assert names["wake"] == names["place"] == names["commit"] == m.n_tasks
    assert names["lock_wait"] == 3 * m.n_tasks == 3 * 40 * NEW
