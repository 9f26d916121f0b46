"""The reduction from a device trace to per-layer numbers, on a synthetic
trace with the planes, lines and stats a TPU profile carries.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import importlib.util
import os
import sys
from types import SimpleNamespace as NS

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import trace  # noqa: E402

MS = 1_000_000  # ns


def ev(name, start_ms, dur_ms, **stats):
    return NS(name=name, start_ns=start_ms * MS, duration_ns=dur_ms * MS,
              stats=list(stats.items()))


def metric(name):
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def tr():
    """Window 0–100 ms.  Device: a draw program 10–40 ms whose ops overlap
    (10–25, 20–40), a fit program 50–70 ms with two cg_solve ops (55–60,
    62–66) and one op outside the scope (50–52), a wave 80–85 ms.  Host:
    bench.draw 5–45, bench.fit 48–75, bench.wait 75–100."""
    draw = "jit__pathwise_samples_chunked"
    fit = "jit__fit_chunk"
    ops = [
        ev("fusion.1", 10, 15, hlo_module=draw, long_name="a"),
        ev("scatter.2", 20, 20, hlo_module=draw, long_name="b"),
        ev("copy.3", 50, 2, hlo_module=fit, tf_op="jit(_fit_chunk)/while/body/x"),
        ev("fusion.4", 55, 5, hlo_module=fit,
           tf_op="jit(_fit_chunk)/while/body/cg_solve/while/body/mul"),
        ev("fusion.5", 62, 4, hlo_module=fit,
           tf_op="jit(_fit_chunk)/while/body/cg_solve/while/body/add"),
        ev("gram_block", 80, 3, hlo_module="jit__engine_step"),
        ev("fusion.6", 83, 2, hlo_module="jit__engine_step"),
    ]
    modules = [ev(f"{draw}(1)", 10, 30), ev(f"{fit}(2)", 50, 20),
               ev("jit__engine_step(3)", 80, 5)]
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=modules),
        NS(name="XLA Ops", events=ops),
        NS(name="Steps", events=[ev("0", 0, 100)]),
    ])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.window", 0, 100), ev("bench.draw", 5, 40),
        ev("bench.fit", 48, 27), ev("bench.wait", 75, 25),
        ev("unrelated", 0, 100),
    ])])
    return trace.from_planes([NS(name="/host:metadata", lines=[]), device,
                              host])


def test_window_and_busy_union(tr):
    assert tr.window_s == pytest.approx(0.100)
    # Busy: 10–40 (overlapping ops merged), 50–52, 55–60, 62–66, 80–85.
    flat = [t for span in tr.busy_intervals(0) for t in span]
    assert flat == pytest.approx([0.010, 0.040, 0.050, 0.052, 0.055, 0.060,
                                  0.062, 0.066, 0.080, 0.085])
    assert tr.busy_s() == pytest.approx(0.046)
    assert tr.idle_share() == pytest.approx(0.54)


def test_idle_gaps_by_innermost_annotation(tr):
    gaps = dict(tr.idle_gaps())
    # 0–10 ms: midpoint 5 is bench.draw's start → inside it.
    # 40–50: midpoint 45 → bench.draw ends at 45 → covered (≥).
    assert gaps["bench.draw"] == pytest.approx(0.020)
    # 52–55, 60–62 and 66–80 (mid 73 < 75) are in bench.fit.
    assert gaps["bench.fit"] == pytest.approx(0.019)
    # 85–100 in bench.wait.
    assert gaps["bench.wait"] == pytest.approx(0.015)
    assert sum(gaps.values()) == pytest.approx(0.054)


def test_program_and_scope_time(tr):
    assert tr.program_s("_pathwise_samples_chunked") == pytest.approx(0.030)
    assert tr.program_runs("_pathwise_samples_chunked") == 1
    assert tr.program_s("_fit_chunk") == pytest.approx(0.020)
    assert tr.scope_s("cg_solve") == pytest.approx(0.009)
    # A scope name only matches whole path components.
    assert tr.scope_s("cg") == 0.0
    assert tr.kernel_s("gram_block", "_engine_step") == pytest.approx(0.003)
    assert tr.kernel_s("gram_block", "_fit_chunk") == 0.0


def test_top_ops(tr):
    top = tr.top_ops()
    assert top[0] == ["jit__pathwise_samples_chunked/scatter.2",
                      pytest.approx(0.020)]
    assert len(top) == 7


def test_window_clips_events():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.window", 10, 20)])])
    device = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[
        ev("fusion.1", 0, 15, hlo_module="m"), ev("fusion.2", 25, 30,
                                                  hlo_module="m")])])
    t = trace.from_planes([device, host])
    assert t.busy_s() == pytest.approx(0.010)
    assert t.idle_share() == pytest.approx(0.5)


def test_readers_on_the_trace(tr):
    run = NS(trace=tr, counts={"cg_iters": 3, "rows": 4096, "slots": 80,
                               "rhs": 9},
             peaks={"hbm_bytes_per_s": 819e9},
             config={"serving": {"batch": 64, "capacity": 4096},
                     "walks": {"n_walkers": 16, "l_max": 4}})
    assert metric("draw_ms.bo").read(run) == pytest.approx(30.0)
    assert metric("device_idle_share.fit").read(run) == pytest.approx(54.0)
    assert metric("wave_ms.serve").read(run) == pytest.approx(5.0)
    roof = metric("cg_iter_roofline.fit")
    least = roof.least_bytes(4096, 80, 9)
    assert roof.read(run) == pytest.approx(
        100 * least / 819e9 / (0.009 / 3))
    gram = metric("gram_block_roofline.serve")
    assert gram.read(run) == pytest.approx(
        100 * gram.least_bytes(64, 4096, 80) / 819e9 / 0.003)


def test_readers_find_nothing_on_an_empty_trace():
    empty = trace.from_planes([NS(name="/host:CPU", lines=[])])
    run = NS(trace=empty, counts={"cg_iters": 3, "rows": 1, "slots": 1,
                                  "rhs": 1},
             peaks={"hbm_bytes_per_s": 819e9},
             config={"serving": {"batch": 1, "capacity": 1},
                     "walks": {"n_walkers": 1, "l_max": 1}})
    for name in ("draw_ms.bo", "device_idle_share.bo", "cg_iter_roofline.fit",
                 "wave_ms.serve", "observe_ms.serve",
                 "gram_block_roofline.serve"):
        assert metric(name).read(run) is None, name


def test_least_bytes_hand_counts():
    # CG iteration, T=2 rows, K=3 slots, R=2 columns:
    # Φ_x 2·3·8 = 48; matvec in+out 2·2·2·4 = 32; x, r, p updates
    # 6·2·2·4 = 96; Jacobi diagonal 2·4 = 8.
    assert metric("cg_iter_roofline.fit").least_bytes(2, 3, 2) == 184
    # gram_block, q=2 query rows, c=3 train rows, K=4 slots:
    # rows (2+3)·4·8 = 160; output 2·3·4 = 24.
    assert metric("gram_block_roofline.serve").least_bytes(2, 3, 4) == 184


def test_ops_without_module_names_take_their_program_span():
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit__engine_step(7)", 10, 10)]),
        NS(name="XLA Ops", events=[ev("gram_block", 12, 3),
                                   ev("fusion.1", 30, 2)]),
    ])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.window", 0, 40)])])
    t = trace.from_planes([device, host])
    assert t.kernel_s("gram_block", "_engine_step") == pytest.approx(0.003)
    assert t.ops[0][1].program == ""


def test_program_runs_from_run_ids_without_a_modules_line():
    device = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[
        ev("a", 1, 1, hlo_module="jit__engine_step", run_id=5),
        ev("b", 2, 1, hlo_module="jit__engine_step", run_id=5),
        ev("a", 5, 1, hlo_module="jit__engine_step", run_id=6),
        ev("c", 7, 1, hlo_module="jit__other", run_id=7)])])
    t = trace.from_planes([device])
    assert t.program_runs("_engine_step") == 2
    assert t.program_s("_engine_step") == pytest.approx(0.003)


def test_load_reads_scopes_from_event_metadata(tmp_path):
    """A serialized XSpace as the TPU writes it: an op's name scope is a
    stat of its event metadata (here an interned string, ``ref_value``),
    not of the event; host annotations sit on a thread line."""
    space = trace.xspace_class()()
    dev = space.planes.add(name="/device:TPU:0")
    for key, name in ((1, "tf_op"), (2, "run_id"),
                      (3, "jit(_fit_chunk)/while/body/cg_solve/mul")):
        dev.stat_metadata.add(key=key).value.name = name
    for key, name, scope in ((10, "fusion.4", True), (11, "copy.3", False),
                             (12, "jit__fit_chunk(2)", False)):
        md = dev.event_metadata.add(key=key).value
        md.name = name
        if scope:
            md.stats.add(metadata_id=1, ref_value=3)
    mods = dev.lines.add(name="XLA Modules", timestamp_ns=50 * MS)
    mods.events.add(metadata_id=12, offset_ps=0, duration_ps=20 * MS * 1000)
    ops = dev.lines.add(name="XLA Ops", timestamp_ns=50 * MS)
    ops.events.add(metadata_id=11, offset_ps=0, duration_ps=2 * MS * 1000)
    e = ops.events.add(metadata_id=10, offset_ps=5 * MS * 1000,
                       duration_ps=5 * MS * 1000)
    e.stats.add(metadata_id=2, int64_value=9)
    dev.lines.add(name="Steps").events.add(metadata_id=11, duration_ps=1)
    host = space.planes.add(name="/host:CPU")
    host.stat_metadata.add(key=1).value.name = "unused"
    for key, name in ((1, "bench.window"), (2, "bench.fit"), (3, "other")):
        host.event_metadata.add(key=key).value.name = name
    py = host.lines.add(name="python", timestamp_ns=0)
    py.events.add(metadata_id=1, offset_ps=0, duration_ps=100 * MS * 1000)
    py.events.add(metadata_id=2, offset_ps=48 * MS * 1000,
                  duration_ps=27 * MS * 1000)
    py.events.add(metadata_id=3, offset_ps=0, duration_ps=MS * 1000)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())

    t = trace.load(str(path))
    assert t.window_s == pytest.approx(0.100)
    assert [n for _, _, n in t.host] == ["bench.window", "bench.fit"]
    assert t.scope_s("cg_solve") == pytest.approx(0.005)
    assert t.program_s("_fit_chunk") == pytest.approx(0.020)
    assert t.busy_s() == pytest.approx(0.007)
    assert [o.run for o in t.ops[0]] == ["", "9"]
    assert t.ops[0][1].program == "jit__fit_chunk(2)"


def test_tpu_names_transformed_scopes_and_loops(tmp_path):
    """As the TPU writes an op: its name is the whole HLO instruction and
    its display name the instruction's own; its name scope (``tf_op``) may
    be wrapped by a transform (``jvp(cg_solve)``); a loop is an op of its
    own whose span holds its body's ops, left out of the top ops."""
    space = trace.xspace_class()()
    dev = space.planes.add(name="/device:TPU:0")
    for key, name in ((1, "tf_op"), (2, "hlo_category")):
        dev.stat_metadata.add(key=key).value.name = name
    body = "jit(_fit_chunk)/while/body/closed_call/"
    for key, name, cat, scope in (
            (10, "while.54", "while", ""),
            (11, "fusion.169", "custom fusion",
             body + "jvp(cg_solve)/while/body/scatter-add:"),
            (12, "fusion.151", "custom fusion", body + "jvp()/scatter-add:"),
            (13, "jit__fit_chunk(2)", "", "")):
        md = dev.event_metadata.add(key=key).value
        md.name = f"%{name} = f32[1048576,9] fusion(...)" if cat else name
        md.display_name = name if cat else ""
        if cat:
            md.stats.add(metadata_id=2, str_value=cat)
            md.stats.add(metadata_id=1, str_value=scope)
    mods = dev.lines.add(name="XLA Modules", timestamp_ns=0)
    mods.events.add(metadata_id=13, offset_ps=0, duration_ps=20 * MS * 1000)
    ops = dev.lines.add(name="XLA Ops", timestamp_ns=0)
    for key, start, dur in ((12, 0, 2), (10, 2, 15), (11, 3, 6), (11, 10, 6)):
        ops.events.add(metadata_id=key, offset_ps=start * MS * 1000,
                       duration_ps=dur * MS * 1000)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())

    t = trace.load(str(path))
    assert t.scope_s("cg_solve") == pytest.approx(0.012)
    assert t.scope_s("jvp") == 0.0
    assert t.busy_s() == pytest.approx(0.017)
    top = t.top_ops()
    assert top[0] == ["jit__fit_chunk(2)/fusion.169 "
                      "jvp(cg_solve)/while/body/scatter-add",
                      pytest.approx(0.012)]
    assert [k for k, _ in top if "while.54" in k] == []
    assert len(top) == 2
