"""The harness end to end on the CPU with tiny float32 models: the look
for a chip is skipped, everything else is a run (set-up, window, await,
reference).  A sound run is correct; a run whose timed path is broken
underneath is not."""
import json
import os
import subprocess
import sys
import time

import pytest

from chipbench import cell
from chipbench.spec import ROOT
from chipbench.tests.tiny import tiny_spec

SEED = 2**31 + 5


def _run(family="dense", loop="open", trace=False, tmp_path=None):
    spec = tiny_spec(family, loop)
    result, info = cell.run(spec, SEED, 2.0, trace,
                            t_proc0=time.perf_counter(),
                            trace_dir=str(tmp_path) if tmp_path else None,
                            require_chip=False)
    return result, info


@pytest.mark.parametrize("family,loop", [("dense", "open"),
                                         ("hybrid", "open"),
                                         ("dense", "closed")])
def test_sound_run_is_correct(family, loop):
    result, info = _run(family, loop)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "checks"
    names = set(result["metrics"])
    if loop == "open":
        assert {"ttft_p50_ms", "tpot_p50_ms", "setup_s"} <= names
    else:
        assert {"output_tok_s", "setup_s"} <= names
    assert result["checks"]["logit_gap"]["value"] <= 1e-4


def test_traced_run_reports_per_layer_metrics(tmp_path):
    result, _ = _run(trace=True, tmp_path=tmp_path)
    assert result["correct"]
    assert {"prefill_task_ms", "decode_task_ms.open",
            "compiles_in_window.open"} <= set(result["metrics"])
    assert result["metrics"]["compiles_in_window.open"]["value"] == 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("trace", [False, True],
                         ids=["untraced", "traced"])
def test_run_carries_spans_stamps_and_counters(trace, tmp_path):
    """Every run carries each request's token stamps and the engine's
    counters; only a traced run turns the engine's spans on and carries
    them."""
    result, info = _run(trace=trace, tmp_path=tmp_path)
    assert result["correct"], result["checks"]
    data = info["data"]
    done = [r for r in data.requests if r.finished]
    assert done
    for r in done:
        assert len(r.tokens) == r.n_out
        assert r.tokens[0] == r.first and r.tokens[-1] == r.done
        assert list(r.tokens) == sorted(r.tokens)
    c = data.counters
    assert c["decode_steps"] >= sum(r.n_out - 1 for r in done) > 0
    assert 0 <= c["decode_in_place"] <= c["decode_steps"]
    if trace:
        names = {s.name for s in data.spans}
        assert {"decode.launch", "decode.token_wait", "commit"} <= names
    else:
        assert data.spans is None


def _break_decode(monkeypatch, how):
    from repro.serve import engine as eng
    real = eng.ServingEngine._run_decode

    def broken(self, req, state, tok):
        new_state, nxt = real(self, req, state, tok)
        if how == "token":
            nxt = (nxt + 1) % self.cfg.vocab
            req.out_tokens[-1] = nxt
            return new_state, nxt
        return state, nxt                    # state returned unchanged

    monkeypatch.setattr(eng.ServingEngine, "_run_decode", broken)


@pytest.mark.parametrize("how", ["token", "state"])
def test_broken_decode_is_not_correct(monkeypatch, how):
    _break_decode(monkeypatch, how)
    result, _ = _run()
    assert not result["correct"]
    gap = result["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


def test_never_finished_request_is_not_correct(monkeypatch):
    from repro.serve import engine as eng
    real = eng.ServingEngine._run_decode
    calls = {"n": 0}

    def raising(self, req, state, tok):
        calls["n"] += 1
        if calls["n"] == 7:
            raise RuntimeError("planted payload fault")
        return real(self, req, state, tok)

    monkeypatch.setattr(eng.ServingEngine, "_run_decode", raising)
    monkeypatch.setattr(cell, "AWAIT_S", 2.0)
    result, _ = _run()
    assert not result["correct"]
    assert result["checks"]["payload_errors"]["value"] >= 1


def test_no_chip_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "stablelm-3b.chat_poisson", "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode == 2, p.stderr
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr


def test_every_cell_and_metric_resolves():
    from chipbench import spec as spec_mod
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        s = spec_mod.load(w["name"], ROOT)
        assert s.end_to_end and s.per_layer
        assert any(m["name"] == "setup_s" for m in s.end_to_end)
        for m in s.end_to_end + s.per_layer:
            assert callable(spec_mod.reader(m["name"]))
