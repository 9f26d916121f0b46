"""The observability layer (DESIGN.md §3.10): registry, spans, taps, report.

Contract under test (ISSUE 8 acceptance):
  * disabled is free: an instrumented jit lowers to *callback-less* HLO and
    returns bit-identical values to the enabled trace (same math, different
    cache entries), with a lenient min-of-N wall-clock gate vs a bare
    function;
  * spans nest (slash-joined path, depth) and close inner-first in the
    event stream, and no-op both when disabled and under an active trace;
  * histogram buckets are the fixed log-spaced edges, edge-inclusive, with
    an overflow slot and [min, max]-clamped percentiles;
  * a recorded JSONL flight record round-trips: meta first, one trailing
    summary, every event schema-valid (``report.validate`` returns []);
  * taps fire under jit on both the xla and pallas-interpret spmv backends
    and count *executions*, not compilations;
  * the ``solver.cg`` tap mirrors the returned CGResult fields.
"""
import contextlib
import json
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs, solvers
from repro.core import linops, modulation, walks
from repro.graphs import generators
from repro.kernels import dispatch
from repro.obs import registry as obs_registry
from repro.obs import report, taps


@pytest.fixture(autouse=True)
def clean_obs(monkeypatch):
    """Every test starts disabled with an empty registry and no env flag."""
    monkeypatch.delenv("REPRO_OBS", raising=False)
    obs.reset_enabled()
    obs.REGISTRY.reset()
    yield
    obs.reset_enabled()
    obs.REGISTRY.reset()


@pytest.fixture()
def ring_sink():
    sink = obs.RingBufferSink(256)
    obs.REGISTRY.add_sink(sink)
    yield sink
    obs.REGISTRY.remove_sink(sink)


def _spans(sink):
    """The span events of a sink (an enabled sink also receives a
    ``jit.compile`` event per compile)."""
    return [e for e in sink.events if e["type"] == "span"]


# ---------------------------------------------------------------------------
# Enablement resolution (context > global > env > off).
# ---------------------------------------------------------------------------


def test_enablement_resolution(monkeypatch):
    assert not obs.enabled()                      # default: off
    monkeypatch.setenv("REPRO_OBS", "1")
    assert obs.enabled()                          # env turns it on
    obs.disable()
    assert not obs.enabled()                      # global beats env
    obs.enable()
    assert obs.enabled()
    with obs.tap_scope(False):
        assert not obs.enabled()                  # context beats global
        with obs.tap_scope(True):
            assert obs.enabled()
        assert not obs.enabled()
    assert obs.enabled()


def test_module_conveniences_honour_switch():
    obs.inc("c")
    obs.gauge("g", 1.0)
    obs.observe("h", 1.0)
    snap = obs.REGISTRY.snapshot()
    assert not snap["counters"] and not snap["gauges"] and not snap["histograms"]
    obs.enable()
    obs.inc("c", 2)
    obs.gauge("g", 3.0)
    obs.observe("h", 0.5)
    snap = obs.REGISTRY.snapshot()
    assert snap["counters"]["c"] == 2
    assert snap["gauges"]["g"] == 3.0
    assert snap["histograms"]["h"]["count"] == 1


def test_label_key_folding():
    obs.enable()
    obs.inc("walks", labels={"scheme": "iid", "backend": "xla"})
    obs.inc("walks", labels={"backend": "xla", "scheme": "iid"})
    snap = obs.REGISTRY.snapshot()
    # Insertion order of the labels dict must not matter: one sorted key.
    assert snap["counters"] == {"walks{backend=xla,scheme=iid}": 2}


# ---------------------------------------------------------------------------
# Histogram buckets and percentiles.
# ---------------------------------------------------------------------------


def test_bucket_edges_are_fixed_log_spaced():
    edges = obs.log_buckets(1e-7, 1e3, 5)
    assert edges == obs.DEFAULT_BUCKETS
    assert len(edges) == 51                       # 10 decades x 5 + fencepost
    assert edges[0] == pytest.approx(1e-7)
    assert edges[-1] == pytest.approx(1e3)
    ratios = [edges[i + 1] / edges[i] for i in range(len(edges) - 1)]
    assert all(r == pytest.approx(10 ** 0.2) for r in ratios)


def test_histogram_bucketing_edge_inclusive_with_overflow():
    h = obs.Histogram(buckets=(1.0, 10.0, 100.0))
    for v in (0.5, 1.0):                          # v <= edge -> that bucket
        h.observe(v)
    h.observe(10.0)
    h.observe(11.0)
    h.observe(1e6)                                # above hi -> overflow slot
    assert h.counts == [2, 1, 1, 1]
    assert h.count == 5
    assert h.total == pytest.approx(0.5 + 1.0 + 10.0 + 11.0 + 1e6)
    assert h.vmin == 0.5 and h.vmax == 1e6


def test_histogram_percentiles_clamped_and_monotone():
    h = obs.Histogram()
    h.observe(0.25)
    # A single observation: every percentile is clamped to that exact value.
    assert h.percentile(0.5) == h.percentile(0.99) == 0.25
    rng = np.random.default_rng(0)
    vals = rng.lognormal(mean=-5, sigma=2, size=500)
    for v in vals:
        h.observe(v)
    p50, p95, p99 = h.percentile(0.5), h.percentile(0.95), h.percentile(0.99)
    assert h.vmin <= p50 <= p95 <= p99 <= h.vmax
    # Bucket error at 5/decade is ~±26%; allow 2x against the exact quantile.
    exact = np.percentile(np.append(vals, 0.25), 95)
    assert p95 == pytest.approx(exact, rel=1.0)
    empty = obs.Histogram()
    assert np.isnan(empty.percentile(0.5))


def test_histogram_snapshot_fields():
    h = obs.Histogram()
    snap = h.snapshot()
    assert snap["count"] == 0 and snap["p50"] is None and snap["min"] is None
    h.observe(2.0)
    snap = h.snapshot()
    assert snap == {
        "count": 1, "sum": 2.0, "min": 2.0, "max": 2.0,
        "p50": 2.0, "p95": 2.0, "p99": 2.0,
    }


# ---------------------------------------------------------------------------
# Spans: nesting, ordering, disabled/under-trace no-ops.
# ---------------------------------------------------------------------------


def test_span_nesting_and_ordering(ring_sink):
    obs.enable()
    with obs.span("outer") as sp:
        sp.note(fill=0.5)
        with obs.span("inner"):
            time.sleep(0.01)
    events = list(ring_sink.events)
    assert [e["name"] for e in events] == ["inner", "outer"]  # inner closes 1st
    inner, outer = events
    assert inner["path"] == "outer/inner" and inner["depth"] == 1
    assert outer["path"] == "outer" and outer["depth"] == 0
    assert inner["seq"] < outer["seq"]
    assert outer["attrs"] == {"fill": 0.5}
    assert not inner["blocked"]
    # Durations nest too: the outer span contains the inner sleep.
    assert outer["dur_s"] >= inner["dur_s"] >= 0.01
    snap = obs.REGISTRY.snapshot()
    assert snap["histograms"]["span.inner"]["count"] == 1
    assert snap["histograms"]["span.outer"]["count"] == 1


def test_span_block_on_records_blocked_flag(ring_sink):
    obs.enable()
    with obs.span("blocked") as sp:
        out = jnp.ones(8) * 2.0
        sp.block_on(out)
    (ev,) = _spans(ring_sink)
    assert ev["blocked"] is True


def test_span_disabled_is_noop(ring_sink):
    with obs.span("nope") as sp:
        sp.note(x=1)              # the null span still accepts the API
        sp.block_on(jnp.ones(2))
    assert not ring_sink.events
    assert not obs.REGISTRY.snapshot()["histograms"]


def test_span_noop_under_active_trace(ring_sink):
    obs.enable()

    @jax.jit
    def f(x):
        with obs.span("traced"):   # wall-clock is meaningless here
            return x * 2

    np.testing.assert_allclose(f(jnp.ones(4)), 2.0)
    assert not _spans(ring_sink)
    assert "span.traced" not in obs.REGISTRY.snapshot()["histograms"]


# ---------------------------------------------------------------------------
# Taps under jit: the zero-overhead disabled contract.
# ---------------------------------------------------------------------------


def _instrumented(x, obs_tap=False):
    with obs.tap_scope(obs_tap):
        y = jnp.cumsum(x * 2.0)
        taps.tap_dict("t", {"total": y[-1], "ok": y[-1] > 0}, hist=("total",))
        return y


def _bare(x):
    return jnp.cumsum(x * 2.0)


def test_disabled_trace_stages_no_callbacks():
    jit_i = jax.jit(_instrumented, static_argnames=("obs_tap",))
    x = jnp.arange(16, dtype=jnp.float32)
    off = jit_i.lower(x, obs_tap=False).as_text()
    on = jit_i.lower(x, obs_tap=True).as_text()
    assert "callback" not in off    # no host crossing staged when disabled
    assert "callback" in on


def test_disabled_and_enabled_traces_bit_identical():
    obs.enable()
    jit_i = jax.jit(_instrumented, static_argnames=("obs_tap",))
    x = jnp.linspace(-1.0, 3.0, 64)
    got_on = np.asarray(jit_i(x, obs_tap=obs.enabled()))
    obs.disable()
    got_off = np.asarray(jit_i(x, obs_tap=obs.enabled()))
    assert got_on.tobytes() == got_off.tobytes()
    np.testing.assert_array_equal(got_off, np.asarray(jax.jit(_bare)(x)))


def test_disabled_overhead_gate():
    """Min-of-N wall clock: instrumented-but-disabled ~= bare.

    The structural guarantee is the callback-less HLO above; this is the
    belt-and-braces timing check, lenient (2x on a microsecond dispatch)
    because shared CI runners jitter."""
    jit_i = jax.jit(_instrumented, static_argnames=("obs_tap",))
    jit_b = jax.jit(_bare)
    x = jnp.arange(4096, dtype=jnp.float32)
    jax.block_until_ready(jit_i(x, obs_tap=False))
    jax.block_until_ready(jit_b(x))

    def best_of(fn, reps=30):
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            ts.append(time.perf_counter() - t0)
        return min(ts)

    t_bare = best_of(lambda: jit_b(x))
    t_inst = best_of(lambda: jit_i(x, obs_tap=False))
    assert t_inst <= t_bare * 2.0 + 1e-4


@pytest.mark.parametrize("backend", ["xla", "pallas-interpret"])
def test_tap_under_jit_both_backends(backend):
    """The instrumented walk sampler: taps fire from inside jit on both
    spmv backends, and enabling obs does not change the sampled trace."""
    g = generators.barabasi_albert(64, m=2, seed=0)
    key = jax.random.PRNGKey(0)
    with dispatch.use_backend(backend):
        t_off = walks.sample_walks(g, key, n_walkers=2, p_halt=0.5, l_max=3)
        assert not obs.REGISTRY.snapshot()["counters"]   # disabled: silent
        obs.enable()
        t_on = walks.sample_walks(g, key, n_walkers=2, p_halt=0.5, l_max=3)
    snap = obs.REGISTRY.snapshot()
    label = f"{{backend={backend},scheme=iid}}"
    assert snap["counters"][f"walks.rows_sampled{label}"] == 64
    assert snap["counters"][f"walks.walkers_launched{label}"] == 128
    assert snap["histograms"]["span.walks.sample"]["count"] == 1
    np.testing.assert_array_equal(np.asarray(t_off.cols), np.asarray(t_on.cols))
    np.testing.assert_array_equal(np.asarray(t_off.loads), np.asarray(t_on.loads))


def test_pathwise_chunked_streams_phi_once():
    """A chunked pathwise draw samples every row of Φ once, plus the T
    training rows: one streamed pass over N, not a prior pass and a
    correction pass."""
    from repro.gp import posterior

    n, chunk, t = 128, 32, 8
    g = generators.ring(n, k=2)
    cfg = walks.WalkConfig(n_walkers=4, p_halt=0.3, l_max=3)
    mod = modulation.diffusion(l_max=3)
    f = mod(mod.init(None))
    obs.enable()
    out = posterior.pathwise_samples_chunked(
        g, jnp.arange(t), f, 0.1, jnp.ones(t), jax.random.PRNGKey(0),
        jax.random.PRNGKey(1), cfg, chunk=chunk, n_samples=2)
    jax.block_until_ready(out)
    rows = obs.REGISTRY.snapshot()["counters"][
        "walks.rows_sampled{backend=xla,scheme=iid}"]
    assert rows == -(-n // chunk) * chunk + t


def test_count_counts_executions_not_compilations():
    obs.enable()

    @jax.jit
    def f(x):
        taps.count("execs")
        return x + 1

    for i in range(3):
        jax.block_until_ready(f(jnp.float32(i)))
    # One compilation, three executions -> the counter must read 3.
    assert obs.REGISTRY.snapshot()["counters"]["execs"] == 3


def test_tap_tick_host_side_sampling():
    reg = obs.Registry()
    hits = [reg.tap_tick("x", 4) for _ in range(8)]
    assert hits == [True, False, False, False, True, False, False, False]
    assert all(reg.tap_tick("y", 1) for _ in range(3))


def test_solver_tap_mirrors_cg_result(ring_sink):
    g = generators.ring(256, k=3)
    cfg = walks.WalkConfig(n_walkers=4, p_halt=0.3, l_max=4)
    tr = walks.sample_walks_for_nodes(
        g, jnp.arange(32), jax.random.PRNGKey(0),
        cfg.n_walkers, cfg.p_halt, cfg.l_max, cfg.reweight,
    )
    mod = modulation.diffusion(l_max=cfg.l_max)
    f = mod(mod.init(jax.random.PRNGKey(1)))
    h = linops.shifted(tr, f, jnp.asarray(1e-1), g.n_nodes)
    b = jnp.asarray(np.random.default_rng(2).standard_normal(32), jnp.float32)
    obs.enable()
    strategy = solvers.SolveStrategy(tol=1e-6, max_iters=200,
                                     preconditioner="jacobi")
    res = solvers.solve(h, b, strategy)
    jax.block_until_ready(res.x)
    evs = [e for e in ring_sink.events
           if e["type"] == "tap" and e["name"] == "solver.cg"]
    assert evs, "solver.cg tap did not fire"
    ev = evs[-1]
    assert ev["values"]["iters"] == int(res.iters)
    assert ev["values"]["converged"] == bool(jnp.all(res.converged))
    assert ev["meta"]["preconditioner"] == "jacobi"
    assert ev["meta"]["precond_rank"] == int(res.precond_rank)
    assert ev["meta"]["max_iters"] == 200
    snap = obs.REGISTRY.snapshot()
    assert snap["histograms"]["solver.cg.iters"]["count"] >= 1


# ---------------------------------------------------------------------------
# Flight recorder: JSONL round-trip + schema validation.
# ---------------------------------------------------------------------------


def test_recording_roundtrip_schema(tmp_path):
    path = str(tmp_path / "run.jsonl")
    with obs.recording(path) as reg:
        assert reg is obs.REGISTRY and obs.enabled()
        obs.inc("c", 2)
        with obs.span("work"):
            jax.block_until_ready(
                jax.jit(_instrumented, static_argnames=("obs_tap",))(
                    jnp.ones(8), obs_tap=obs.enabled()
                )
            )
    assert not obs.enabled()                       # state restored on exit
    assert report.validate(path) == []
    events = report.read_events(path)
    assert events[0]["type"] == "meta"
    assert events[0]["spmv_backend"] in dispatch.VALID_BACKENDS
    assert events[-1]["type"] == "summary"
    assert [e["seq"] for e in events] == sorted(e["seq"] for e in events)
    types = {e["type"] for e in events}
    assert {"meta", "span", "tap", "summary"} <= types
    metrics = events[-1]["metrics"]
    assert metrics["counters"]["c"] == 2
    assert metrics["histograms"]["span.work"]["count"] == 1
    # The rendered table is derivable from the recorded summary alone.
    table = report.summary(metrics)
    assert "work" in table and "c" in table


def test_recording_without_path_uses_ring_only(tmp_path):
    obs.REGISTRY.inc("stale", 9)
    with obs.recording(None) as reg:
        obs.inc("x")
    assert not list(tmp_path.iterdir())            # nothing written to disk
    # fresh=True wiped pre-existing metrics; the window's own survive exit.
    counters = reg.snapshot()["counters"]
    assert counters == {"x": 1}


def test_validate_catches_violations(tmp_path):
    p = tmp_path / "bad.jsonl"
    p.write_text("")
    assert report.validate(str(p))                 # empty file
    p.write_text('{"type": "span", "name": "x"}\n')
    errs = report.validate(str(p))
    assert any("meta" in e for e in errs)          # no leading meta
    assert any("summary" in e for e in errs)       # no trailing summary
    assert any("missing" in e for e in errs)       # span lacks required fields
    p.write_text("not json\n")
    assert any("unparseable" in e for e in report.validate(str(p)))
    good = tmp_path / "good.jsonl"
    with obs.recording(str(good)):
        obs.inc("ok")
    assert report.main(["--validate", str(good)]) == 0
    assert report.main(["--validate", str(p)]) == 1


def test_fit_step_events_recorded(tmp_path):
    g = generators.ring(128, k=2)
    cfg = walks.WalkConfig(n_walkers=4, p_halt=0.3, l_max=3)
    tr = walks.sample_walks_for_nodes(
        g, jnp.arange(24), jax.random.PRNGKey(0),
        cfg.n_walkers, cfg.p_halt, cfg.l_max, cfg.reweight,
    )
    mod = modulation.diffusion(l_max=cfg.l_max)
    y = jnp.asarray(np.random.default_rng(0).standard_normal(24), jnp.float32)
    path = str(tmp_path / "fit.jsonl")
    from repro.gp import mll

    with obs.recording(path):
        mll.fit_hyperparams(tr, mod, y, g.n_nodes, jax.random.PRNGKey(1),
                            steps=2, chunk=2)
    assert report.validate(path) == []
    events = report.read_events(path)
    fits = [e for e in events if e["type"] == "fit_step"]
    assert len(fits) == 2
    for i, ev in enumerate(fits, 1):
        assert ev["step"] == i
        assert np.isfinite(ev["loss"])
        assert ev["cg_iters"] >= 1
        assert isinstance(ev["cg_converged"], bool)


# ---------------------------------------------------------------------------
# The two switches: annotations, scopes and the compile counter always;
# recording, blocking and taps only with observability enabled.
# ---------------------------------------------------------------------------


class _Recorder:
    """Stands in for jax.profiler.TraceAnnotation and block_until_ready."""

    def __init__(self):
        self.annotations, self.blocked = [], []

    def annotation(self, name):
        self.annotations.append(name)
        return contextlib.nullcontext()

    def block(self, value):
        self.blocked.append(value)
        return value


@pytest.fixture()
def recorder(monkeypatch):
    rec = _Recorder()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", rec.annotation)
    monkeypatch.setattr(jax, "block_until_ready", rec.block)
    return rec


@pytest.mark.parametrize("on", [False, True], ids=["obs_off", "obs_on"])
def test_span_annotates_always_and_records_only_when_enabled(
        ring_sink, recorder, on):
    if on:
        obs.enable()
    with obs.span("outer"):
        with obs.span("inner", block=jnp.ones(2)):
            pass
    assert recorder.annotations == ["repro.outer", "repro.inner"]
    assert len(recorder.blocked) == int(on)
    assert [e["name"] for e in _spans(ring_sink)] == (
        ["inner", "outer"] if on else [])
    hists = obs.REGISTRY.snapshot()["histograms"]
    assert ("span.inner" in hists) == on


def test_span_start_lines_up_with_the_profile(ring_sink, tmp_path):
    """A span event's ``start_ns`` and the span's annotation in the
    profile agree once the profile's times, which count from its
    ``profile_start_time``, are shifted by it."""
    import glob

    obs.enable()
    with jax.profiler.trace(str(tmp_path)):
        time.sleep(0.01)
        with obs.span("aligned"):
            time.sleep(0.02)
    (ev,) = _spans(ring_sink)
    (xplane,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    prof = jax.profiler.ProfileData.from_file(xplane)
    start = dict(prof.find_plane_with_name("Task Environment").stats)[
        "profile_start_time"]
    (ann,) = [e for pl in prof.planes if pl.name.startswith("/host:")
              for ln in pl.lines for e in ln.events
              if e.name == "repro.aligned"]
    assert ann.duration_ns >= 0.02e9
    assert abs(start + ann.start_ns - ev["start_ns"]) < 5e6   # within 5 ms


def test_compile_counter_counts_compiles_not_calls():
    """Always on: a fresh jit compiles once under its function's name and
    a cached call compiles nothing, whatever the switch says."""
    def fresh_fn_for_the_counter(x):
        return x * 3.0 + 1.0

    f = jax.jit(fresh_fn_for_the_counter)
    x = jnp.arange(5.0)
    before = obs.compiles()
    jax.block_until_ready(f(x))
    mid = obs.compiles()
    jax.block_until_ready(f(x))
    after = obs.compiles()
    (name,) = [k for k in mid["by_function"]
               if "fresh_fn_for_the_counter" in k]
    assert name not in before["by_function"]
    assert mid["by_function"][name]["count"] == 1
    assert mid["by_function"][name]["seconds"] > 0
    assert mid["count"] == before["count"] + 1
    assert after["count"] == mid["count"]
    end_ns, _, fun = mid["recent"][-1]
    assert fun == name and end_ns <= time.time_ns()


def test_compile_event_recorded_when_enabled(tmp_path):
    path = str(tmp_path / "run.jsonl")
    with obs.recording(path):
        jax.block_until_ready(jax.jit(lambda v: v - 7.0)(jnp.ones(3)))
    assert report.validate(path) == []
    compiles = [e for e in report.read_events(path)
                if e["type"] == "jit.compile"]
    assert compiles and all(e["dur_s"] > 0 for e in compiles)


def _op_names(lowered) -> str:
    return "\n".join(re.findall(r'op_name="([^"]*)"',
                                lowered.compile().as_text()))


def _fit_chunk_lowered():
    from repro.gp import mll
    from repro.optim.adamw import AdamW

    g = generators.ring(128, k=2)
    tr = walks.sample_walks_for_nodes(g, jnp.arange(16), jax.random.PRNGKey(0),
                                      4, 0.3, 3, True)
    mod = modulation.diffusion(l_max=3)
    params = mll.init_hyperparams(mod, jax.random.PRNGKey(1), 0.1)
    opt = AdamW(lr=0.05)
    return mll._fit_chunk.lower(
        params, opt.init(params), jax.random.PRNGKey(2), tr, jnp.ones(16),
        jnp.ones(16), jnp.zeros((16, 3)), mod=mod, opt=opt, n_nodes=128,
        n_probes=2, strategy=solvers.MLL_DEFAULT, chunk=2,
        spmv_backend="xla", obs_tap=obs.enabled())


def _pathwise_chunked_lowered():
    from repro.gp import posterior

    g = generators.ring(128, k=2)
    cfg = walks.WalkConfig(n_walkers=4, p_halt=0.3, l_max=3)
    mod = modulation.diffusion(l_max=3)
    f = mod(mod.init(None))
    return posterior._pathwise_samples_chunked.lower(
        g, jnp.arange(8), f, 0.1, jnp.ones(8), jax.random.PRNGKey(0),
        jax.random.PRNGKey(1), jnp.ones(8), cfg=cfg, chunk=32, n_samples=1,
        strategy=solvers.POSTERIOR_DEFAULT, spmv_backend="xla",
        obs_tap=obs.enabled())


@pytest.mark.parametrize("program,want", [
    (_fit_chunk_lowered,
     [r"cg_solve\)?/while/body/grf_khat/grf_phi_t/", "grf_payload"]),
    (_pathwise_chunked_lowered,
     ["grf_walks", "/grf_phi/", "grf_phi_t", "cg_solve/while/body/grf_khat"]),
], ids=["fit_chunk", "pathwise_chunked"])
def test_programs_carry_product_scopes_and_no_callback(program, want):
    lowered = program()
    names = _op_names(lowered)
    for pat in want:
        assert re.search(pat, names), pat
    assert "callback" not in lowered.as_text()


def test_cache_key_carries_the_scope_names():
    """The persistent compilation cache strips op metadata from its key; the
    scope names go in through jax's key hook, so an executable compiled
    under other names is never served in place of this program's."""
    from jax._src import cache_key

    key = cache_key.custom_hook()
    assert all(name in key for name in dispatch.NAMED_SCOPES)
