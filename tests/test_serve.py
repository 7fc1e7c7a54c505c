"""Serving engine: requests complete; PTT steers prefill away from a
slowed submesh; overload degrades gracefully through the brownout
ladder instead of growing an unbounded queue."""
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.core import tpu_pod_slices
from repro.serve import BrownoutConfig, ServingEngine


@pytest.fixture(scope="module")
def engine_cfg():
    return ARCHS["xlstm-125m"].reduced()


def test_requests_complete_and_decode_chains(engine_cfg):
    topo = tpu_pod_slices(2, 2)
    eng = ServingEngine(engine_cfg, topo, scheduler="DAM-P", max_len=48)
    rng = np.random.default_rng(0)
    reqs = [eng.submit(rng.integers(0, engine_cfg.vocab, 16), max_new_tokens=3)
            for _ in range(4)]
    m = eng.run(timeout=300)
    stats = eng.latency_stats()
    assert stats["completed"] == 4
    for r in reqs:
        assert len(r.out_tokens) == 3              # prefill + 2 decode steps
        assert r.t_first_token >= r.t_submit
        assert r.t_done >= r.t_first_token
    # prefill is HIGH and unstealable under DAM-P
    assert any(rec.priority == 1 for rec in m.records)


def test_hlo_analysis_on_toy_program():
    """The roofline extractor counts a scanned matmul exactly."""
    import jax
    import jax.numpy as jnp
    from repro.launch.hlo_analysis import analyze_hlo

    def f(x, w):
        def body(c, _):
            return jnp.tanh(w @ c), None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y

    c = jax.jit(f).lower(jax.ShapeDtypeStruct((128, 128), jnp.float32),
                         jax.ShapeDtypeStruct((128, 128), jnp.float32)).compile()
    res = analyze_hlo(c.as_text())
    want = 7 * 2 * 128 ** 3
    assert res["flops"] == pytest.approx(want, rel=1e-6)
    assert res["collective_bytes"]["total"] == 0


def test_deadline_admission_rejects_hopeless_requests(engine_cfg):
    """A deadline below even the PTT-best-case estimate is refused at
    admission: nothing runs for it, it finalizes instantly with the
    ``rejected`` flag, and admitted requests are unaffected."""
    topo = tpu_pod_slices(2, 2)
    eng = ServingEngine(engine_cfg, topo, scheduler="DAM-C", max_len=48)
    rng = np.random.default_rng(2)
    ok = eng.submit(rng.integers(0, engine_cfg.vocab, 16), max_new_tokens=2)
    doomed = [eng.submit(rng.integers(0, engine_cfg.vocab, 16),
                         max_new_tokens=4, deadline_s=1e-5)
              for _ in range(3)]
    for r in doomed:
        assert r.rejected and r.t_done == r.t_submit
        assert not r.out_tokens                  # nothing ever ran
    eng.run(timeout=300)
    stats = eng.latency_stats()
    assert stats["completed"] == 1 and stats["rejected"] == 3
    assert stats["deadline_miss"] == 3           # rejections count as misses
    assert len(ok.out_tokens) == 2


def test_deadline_shedding_truncates_decode_chain(engine_cfg):
    """Admitted requests whose deadline passes mid-chain shed their queued
    LOW decode work: the request finalizes truncated (``shed``) instead
    of holding the fleet while it finishes a dead output."""
    topo = tpu_pod_slices(2, 2)
    eng = ServingEngine(engine_cfg, topo, scheduler="DAM-C", max_len=48)
    rng = np.random.default_rng(3)
    # admitted (deadline >> PTT-prior estimate) but the first prefill pays
    # real jit-compile time, far past the deadline -> decodes shed
    reqs = [eng.submit(rng.integers(0, engine_cfg.vocab, 16),
                       max_new_tokens=6, deadline_s=0.02) for _ in range(3)]
    eng.run(timeout=300)
    stats = eng.latency_stats()
    assert stats["rejected"] == 0                # all were admitted
    assert stats["shed"] == 3
    for r in reqs:
        assert r.shed and r.t_done > 0
        assert 1 <= len(r.out_tokens) < 6        # truncated, not empty


def test_forced_overload_backpressure_and_brownout():
    """Synthetic-payload engine driven ~4x past fleet capacity: the
    bounded pending queue rejects with the ``backpressure`` cause, the
    brownout ladder climbs at least to its shed rung, every intervention
    lands in a cause-split counter, and the transition log is a
    contiguous rung walk."""
    topo = tpu_pod_slices(2, 2)                  # 4 slices
    eng = ServingEngine(None, topo, scheduler="DAM-C",
                        max_pending=24,
                        brownout=BrownoutConfig(enter=(0.02, 0.05, 0.10),
                                                exit=(0.01, 0.02, 0.05)),
                        prefill_s=20e-3, decode_s=5e-3)
    # request work = 20 + 4*5 = 40 ms -> capacity ~100 rps on 4 slices;
    # offered 400 rps
    prompts = [np.zeros(8, np.int32)] * 80
    m = eng.run_open_loop(prompts, rate_rps=400.0, max_new_tokens=5,
                          timeout=120)
    assert not m.errors
    s = eng.latency_stats()
    assert s["completed"] + s["rejected"] == 80
    assert s["rejected_backpressure"] > 0        # bounded queue held
    assert s["rejected"] == s["rejected_backpressure"]
    assert s["rejected_deadline"] == 0           # no deadlines in play
    assert s["shed_deadline"] == 0
    assert s["brownout_max_rung"] >= 2           # ladder reached shedding
    # at least one of the LOW-traffic interventions actually degraded
    # output (clamped length or shed chain)
    assert s["shed_brownout"] + s["tokens_clamped"] > 0
    assert s["shed"] == s["shed_brownout"]
    # the transition log is a contiguous walk starting at rung 0, and
    # the stats counted every hop
    prev = 0
    for _t, frm, to in m.brownout_transitions:
        assert frm == prev and to != frm
        prev = to
    assert s["brownout_transitions"] == len(m.brownout_transitions) > 0


def test_warm_start_priming_is_engine_level():
    """``warm_start`` seeds the PTT through the kernel before the first
    request of each type places, so a cold table never auto-wins the
    argmin; explicit ``prime()`` reports zero once warmed."""
    from repro.core import TaskType
    topo = tpu_pod_slices(2, 2)
    eng = ServingEngine(None, topo, scheduler="DAM-C")
    eng.submit(np.zeros(8, np.int32), max_new_tokens=2)
    tbl = eng.sched.ptt.for_type("prefill_16")
    assert all(tbl.get(p) > 0.0 for p in topo.places())
    kinds = {p.kind for p in topo.partitions}
    assert eng.prime(TaskType("prefill_16",
                              serial_time={k: 1e-3 for k in kinds})) == 0
    eng.run(timeout=60)


@pytest.mark.parametrize("raises", [False, True], ids=["clean", "raising"])
def test_serve_launcher_exit_code(monkeypatch, capsys, raises):
    """The launcher reports a run in which a payload raised as a failure:
    the runtime catches the exception so barrier partners never hang, but
    the process must not exit 0."""
    from repro.launch import serve
    monkeypatch.setattr(serve, "use_compile_cache", lambda: None)
    if raises:
        def boom(self, req):
            raise RuntimeError("device fell over")
        monkeypatch.setattr(ServingEngine, "_run_prefill", boom)
    rc = serve.main(["--requests", "2", "--prompt-len", "8",
                     "--new-tokens", "2"])
    err = capsys.readouterr().err
    if raises:
        assert rc != 0
        assert "RuntimeError: device fell over" in err
        assert "2 of 2 requests did not finish" in err
    else:
        assert rc == 0 and "FAILED" not in err


@pytest.mark.parametrize("env_dir", [None, "/srv/jax-cache"],
                         ids=["repo-default", "from-environment"])
def test_compile_cache_directory(monkeypatch, env_dir):
    """The persistent compile cache goes where JAX_COMPILATION_CACHE_DIR
    says (JAX reads it itself; nothing is set in code), and otherwise to
    the fixed ``.jax_cache/`` at the repository root."""
    import jax
    from repro.launch import cache
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    where = cache.use_compile_cache()
    if env_dir is None:
        assert where == str(cache.REPO_CACHE_DIR)
        assert cache.REPO_CACHE_DIR.name == ".jax_cache"
        assert (cache.REPO_CACHE_DIR.parent / "chip_smoke.py").is_file()
        assert calls == [("jax_compilation_cache_dir", where)]
    else:
        assert where == env_dir and calls == []


def test_open_loop_poisson_arrival(engine_cfg):
    """Open-loop serving: continuous submission while the runtime runs;
    per-request latency percentiles land in RunMetrics."""
    topo = tpu_pod_slices(2, 2)
    eng = ServingEngine(engine_cfg, topo, scheduler="DAM-C", max_len=48)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, engine_cfg.vocab, 12) for _ in range(3)]
    m = eng.run_open_loop(prompts, rate_rps=20.0, max_new_tokens=2,
                          timeout=300)
    assert m.n_tasks >= 3                       # prefill + decode tasks ran
    stats = m.request_latency_stats()
    assert stats["completed"] == 3
    for key in ("ttft_ms", "e2e_ms"):
        for p in ("mean", "p50", "p95", "p99"):
            assert stats[key][p] > 0
        assert stats[key]["p50"] <= stats[key]["p99"]
    # engine-side stats agree on completion count and expose percentiles
    es = eng.latency_stats()
    assert es["completed"] == 3
    assert es["ttft_ms_p50"] <= es["ttft_ms_p99"]
