"""The readers of what the program names and counts of itself (name scopes
of its products, compiles), on a serialized trace of the TPU's layout.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import importlib.util
import os
import sys
from types import SimpleNamespace as NS

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, BENCH)

import run as bench_run  # noqa: E402
from harness import program, rooflines, trace  # noqa: E402

MS = 1_000_000  # ns
START_NS = 1_700_000_000_000_000_000    # the profile's start, host clock
DRAW, FIT = "jit__pathwise_samples_chunked", "jit__fit_chunk"
BODY = "jit(_fit_chunk)/while/body/closed_call/"
CHUNK = "jit(_pathwise_samples_chunked)/while/body/closed_call/"

# (program, start ms, duration ms, scope path as the program names it, the
# same path as a program without product scopes names it).  The window is
# 0-100 ms; the draw 10-40, the fit 50-80.
OPS = [
    (DRAW, 10, 12, CHUNK + "grf_walks/jit(_take)/gather:",
     CHUNK + "jit(_take)/gather:"),
    (DRAW, 22, 6, CHUNK + "grf_payload/mul:", CHUNK + "mul:"),
    (DRAW, 28, 8, CHUNK + "grf_phi/reduce_sum:", CHUNK + "reduce_sum:"),
    (DRAW, 36, 2, "jit(_pathwise_samples_chunked)/grf_phi_t/scatter-add:",
     "jit(_pathwise_samples_chunked)/scatter-add:"),
    (DRAW, 38, 2, "jit(_pathwise_samples_chunked)/add:",
     "jit(_pathwise_samples_chunked)/add:"),
    (FIT, 50, 4, BODY + "jvp(cg_solve)/while/body/grf_khat/grf_phi_t/"
     "scatter-add:", BODY + "jvp(cg_solve)/while/body/scatter-add:"),
    (FIT, 54, 2, BODY + "jvp(cg_solve)/while/body/grf_khat/grf_phi/gather:",
     BODY + "jvp(cg_solve)/while/body/gather:"),
    (FIT, 56, 4, BODY + "jvp(cg_solve)/while/body/grf_khat/grf_phi_t/"
     "scatter-add:", BODY + "jvp(cg_solve)/while/body/scatter-add:"),
    (FIT, 62, 3, BODY + "transpose(jvp(grf_khat))/grf_phi_t/gather:",
     BODY + "transpose(jvp())/gather:"),
    (FIT, 66, 1, BODY + "jvp(cg_solve)/while/body/mul:",
     BODY + "jvp(cg_solve)/while/body/mul:"),
]


def write_xplane(directory, scoped: bool) -> str:
    """A profile as a v5e writes it: device ops with their scope (``tf_op``)
    in the op's metadata, the program runs, the host's ``bench.window``
    and the ``Task Environment`` plane with the profile's start."""
    space = trace.xspace_class()()
    dev = space.planes.add(name="/device:TPU:0")
    for key, name in ((1, "tf_op"), (2, "hlo_category")):
        dev.stat_metadata.add(key=key).value.name = name
    ops = dev.lines.add(name="XLA Ops", timestamp_ns=0)
    for key, (_, start, dur, new, old) in enumerate(OPS, 10):
        md = dev.event_metadata.add(key=key).value
        md.name = md.display_name = f"fusion.{key}"
        md.stats.add(metadata_id=2, str_value="custom fusion")
        md.stats.add(metadata_id=1, str_value=new if scoped else old)
        ops.events.add(metadata_id=key, offset_ps=start * MS * 1000,
                       duration_ps=dur * MS * 1000)
    mods = dev.lines.add(name="XLA Modules", timestamp_ns=0)
    for key, (name, start, dur) in enumerate(
            ((f"{DRAW}(1)", 10, 30), (f"{FIT}(2)", 50, 30)), 90):
        dev.event_metadata.add(key=key).value.name = name
        mods.events.add(metadata_id=key, offset_ps=start * MS * 1000,
                        duration_ps=dur * MS * 1000)
    host = space.planes.add(name="/host:CPU")
    host.event_metadata.add(key=1).value.name = trace.WINDOW
    host.lines.add(name="python", timestamp_ns=0).events.add(
        metadata_id=1, offset_ps=0, duration_ps=100 * MS * 1000)
    env = space.planes.add(name="Task Environment")
    env.stat_metadata.add(key=1).value.name = "profile_start_time"
    env.stats.add(metadata_id=1, uint64_value=START_NS)
    path = os.path.join(directory, "plugins", "profile", "1", "t.xplane.pb")
    os.makedirs(os.path.dirname(path))
    with open(path, "wb") as fh:
        fh.write(space.SerializeToString())
    return path


def metric(name):
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def make_run(directory, monkeypatch, scoped=True):
    """A bench_run.Run over the profile in ``directory``, as run.main makes
    it after a traced window."""
    monkeypatch.setattr(bench_run, "TRACE_DIR", str(directory))
    tr = trace.load(write_xplane(str(directory), scoped))
    drv = NS(window_s=0.1, counts={"cg_iters": 2, "steps": 1, "rows": 4096,
                                   "slots": 80, "rhs": 9}, latencies=[])
    cell = NS(config={}, traffic={})
    return bench_run.Run(cell, drv, 20.0, tr, {"hbm_bytes_per_s": 819e9})


def test_scope_names_take_transforms_off():
    assert program.scope_names(
        BODY + "transpose(jvp(grf_khat))/grf_phi_t/gather:x") == [
        "_fit_chunk", "while", "body", "closed_call", "grf_khat",
        "grf_phi_t", "gather"]


def test_nested_scope_time(tmp_path, monkeypatch):
    tr = make_run(tmp_path, monkeypatch).trace
    # Both CG scatters, not the gradient's transposed gather outside CG.
    assert program.scope_s(tr, "grf_phi_t", within="cg_solve") == \
        pytest.approx(0.008)
    assert program.scope_s(tr, "grf_phi_t") == pytest.approx(0.013)
    assert program.scope_s(tr, ("grf_phi", "grf_phi_t"), program=DRAW) == \
        pytest.approx(0.010)
    assert program.scope_s(tr, "grf_walks", within="cg_solve") == 0.0
    # cg_solve as the trace reads it: the new scopes nest inside it.
    assert tr.scope_s("cg_solve") == pytest.approx(0.011)


def test_new_readers_on_the_trace(tmp_path, monkeypatch):
    run = make_run(tmp_path, monkeypatch)
    assert metric("walks_ms.bo").read(run) == pytest.approx(12.0)
    assert metric("phi_ms.bo").read(run) == pytest.approx(10.0)
    assert metric("phi_t_ms.fit").read(run) == pytest.approx(4.0)


def test_compile_readers_split_setup_from_window(tmp_path, monkeypatch):
    from repro import obs

    recent = [(START_NS - 5 * MS, 2.5, "jit(_fit_chunk)"),
              (START_NS - 1 * MS, 0.5, "jit(_pathwise_samples_chunked)"),
              (START_NS + 40 * MS, 0.25, "jit(_late)"),
              (START_NS + 150 * MS, 1.0, "jit(_after_window)")]
    monkeypatch.setattr(obs, "compiles", lambda: {"recent": recent})
    run = make_run(tmp_path, monkeypatch)
    for cell in ("bo", "fit"):
        assert metric(f"window_compiles.{cell}").read(run) == 1
        assert metric(f"setup_compile_s.{cell}").read(run) == \
            pytest.approx(3.0)
    monkeypatch.setattr(obs, "compiles", lambda: {"recent": recent[:2]})
    assert metric("window_compiles.fit").read(run) == 0


def test_a_missing_scope_finds_nothing(tmp_path, monkeypatch):
    """On a program that names its scopes, a trace without them is a fault
    (``bench/run.py`` exits 4)."""
    run = make_run(tmp_path, monkeypatch, scoped=False)
    for name in ("walks_ms.bo", "phi_ms.bo", "phi_t_ms.fit"):
        assert metric(name).read(run) is None, name


def test_a_program_without_the_names_reads_zero(tmp_path, monkeypatch):
    """A program older than the scopes and the compile counter (the
    parent of the change that added them) reads 0, so that its traced run
    still ends with a result."""
    monkeypatch.setattr(program, "names_scopes", lambda: False)
    monkeypatch.setattr(program, "counts_compiles", lambda: False)
    run = make_run(tmp_path, monkeypatch, scoped=False)
    for name in ("walks_ms.bo", "phi_ms.bo", "phi_t_ms.fit",
                 "window_compiles.bo", "window_compiles.fit",
                 "setup_compile_s.bo", "setup_compile_s.fit"):
        assert metric(name).read(run) == 0, name


@pytest.mark.parametrize("name", [
    "draw_ms.bo", "device_idle_share.bo", "device_idle_share.fit",
    "cg_iter_roofline.fit", "cg_iters_per_step.fit"])
def test_existing_readers_read_as_before(tmp_path, monkeypatch, name):
    """The program's product scopes nest inside what the existing readers
    match, so each reads the same value on a trace with them as on the
    same trace without them."""
    before = metric(name).read(make_run(tmp_path / "old", monkeypatch,
                                        scoped=False))
    after = metric(name).read(make_run(tmp_path / "new", monkeypatch))
    assert before is not None and after == before


def test_walk_roofline_on_the_trace(tmp_path, monkeypatch):
    """One draw in the window, 12 ms under ``grf_walks``; 1,000 start nodes
    of degree >= 1, 30 walkers, p_halt 0.15, l_max 5."""
    run = make_run(tmp_path, monkeypatch)
    run.config = {"walks": {"n_walkers": 30, "p_halt": 0.15, "l_max": 5}}
    run.counts = {"walk_rows": 1000}
    moves = 1000 * 30 * (0.85 + 0.85**2 + 0.85**3 + 0.85**4 + 0.85**5)
    want = 100.0 * moves * 12 / 819e9 / 0.012
    assert metric("walk_roofline.bo").read(run) == pytest.approx(want)
    assert want == pytest.approx(0.011547, rel=1e-4)
    run.counts = {}
    assert metric("walk_roofline.bo").read(run) is None
    run = make_run(tmp_path / "unscoped", monkeypatch, scoped=False)
    run.config, run.counts = {"walks": {"n_walkers": 30, "p_halt": 0.15,
                                        "l_max": 5}}, {"walk_rows": 1000}
    assert metric("walk_roofline.bo").read(run) is None


@pytest.mark.parametrize("scheme,moves", [
    ("iid", 2 * (0.75 + 0.75**2 + 0.75**3)),
    ("antithetic", 2 * (0.75 + 0.75**2 + 0.75**3)),
    ("grfspp", 2 * 3)])
def test_walk_least_bytes(scheme, moves):
    """12 B per expected move of each of 2 walkers from each of 7 rows,
    p_halt 0.25, l_max 3; ``grfspp`` walkers never halt."""
    assert rooflines.walk_least_bytes(7, 2, 0.25, 3, scheme) == \
        pytest.approx(7 * moves * 12, rel=1e-15)


def test_walk_rows_leave_out_degree_zero():
    assert rooflines.walk_rows([0, 3, 1, 0, 64483]) == 3
    assert rooflines.walk_least_bytes(
        rooflines.walk_rows([0, 0]), 30, 0.15, 5) == 0
