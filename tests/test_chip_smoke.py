"""chip_smoke.py: refuses to run without a TPU, and its phases pass on a
small ring when called directly (the CPU rehearsal of the chip run).

The script itself never runs on the CPU, so the rehearsal imports it and
drives each phase at N = 4,096; the mesh phase runs in a subprocess with
four forced host devices so the XLA flag does not leak into the suite."""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
SMOKE = ROOT / "chip_smoke.py"
N = 4096


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(ROOT / "src"), env.get("PYTHONPATH")) if p)
    return env


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_exits_nonzero_without_a_tpu():
    out = subprocess.run([sys.executable, str(SMOKE)], env=_env(),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"ok"' not in out.stdout


def test_one_chip_phases_pass_on_a_small_ring():
    cs = _load()
    from repro.graphs import generators

    check = cs.Checks()
    graph = cs.build_graph(N)
    fitted = cs.fit_phase(graph, check, t_train=4 * 64)
    cs.bo_phase(graph, fitted, check, generators.ring(1024, k=3))
    cs.serving_phase(graph, fitted, check)
    assert check.failed == []


MESH_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import importlib.util, json
spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
cs = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cs)
check = cs.Checks()
cs.mesh_phase(cs.build_graph(int(sys.argv[2])), check, 4)
print(json.dumps({"failed": check.failed}))
"""


def test_mesh_phase_passes_on_four_host_devices():
    out = subprocess.run(
        [sys.executable, "-c", MESH_SCRIPT, str(SMOKE), str(N)],
        env=_env(), capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "check mesh.sharded_cg: PASS" in out.stdout
    assert json.loads(out.stdout.strip().splitlines()[-1]) == {"failed": []}
