"""The Pallas kernels dispatch runs on TPU compile for a v5e chip.

Each case compiles one kernel at the shapes ``chip_smoke.py`` drives, for a
described (not attached) v5e, so a kernel the chip's compiler would refuse
fails here without chip time.  The XLA walk sampler is compiled too, and
its gathers counted.  The topology is described inside a fixture,
never at import, so only the worker that runs this file loads the TPU
compiler.  The persistent compilation cache stays off around these
compiles: their entries cannot be read back without a chip.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import dispatch
from repro.kernels.gram_block.gram_block import gram_block
from repro.kernels.walk_sampler.ref import walk_block
from repro.kernels.woodbury_apply.woodbury_apply import woodbury_apply

T_TRAIN = 4000          # 4√N training rows at N = 10⁶
K = 56                  # n_walkers=8 × (l_max=6 + 1) deposit slots
CAPACITY = 128
BATCH = 64
RANK = 128
R = 9                   # 1 + 8 Hutchinson probes


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        # Otherwise the TPU compiler writes its logs under /tmp.
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure: cannot describe
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiles_to_kernel(fn, *args):
    text = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows,cols", [
    (BATCH, CAPACITY),        # a serving wave against the cached train rows
    (1, CAPACITY),            # one observe() append row
    (CAPACITY, CAPACITY),     # refit's square Gram
    (T_TRAIN, T_TRAIN),       # chip_smoke's dense K̂_train reference
])
def test_gram_block_compiles_for_v5e(one_chip, rows, cols):
    s = _spec
    _compiles_to_kernel(
        gram_block,
        s(one_chip, (rows, K)), s(one_chip, (rows, K), jnp.int32),
        s(one_chip, (cols, K)), s(one_chip, (cols, K), jnp.int32),
    )


def test_walk_block_gathers_once_per_move_for_v5e(one_chip):
    """The BO draw's walk chunk (10⁶-node grid, max degree 4, 65,536 start
    nodes × 30 walkers, l_max 5) compiles to one gather over the start
    nodes' degrees and one packed-row gather per live move: l_max + 1.
    Three reads per move (degree, neighbour, weight) would be 3·l_max."""
    n, max_deg, m, l_max = 10**6, 4, 65536, 5
    fn = jax.jit(functools.partial(walk_block, n_walkers=30, p_halt=0.15,
                                   l_max=l_max))
    text = fn.lower(
        _spec(one_chip, (n * max_deg, 3), jnp.int32),
        _spec(one_chip, (n,), jnp.int32),
        _spec(one_chip, (m,), jnp.int32),
        _spec(one_chip, (), jnp.uint32),
    ).compile().as_text()
    gathers = [line for line in text.splitlines()
               if re.search(r"\bgather\(", line)]
    assert len(gathers) == l_max + 1, gathers


@pytest.mark.parametrize("rhs", [R, None])
def test_woodbury_apply_compiles_for_v5e(one_chip, rhs):
    v = (T_TRAIN, rhs) if rhs else (T_TRAIN,)
    _compiles_to_kernel(
        woodbury_apply,
        _spec(one_chip, (T_TRAIN, RANK)), _spec(one_chip, (T_TRAIN,)),
        _spec(one_chip, (RANK, RANK)), _spec(one_chip, v),
    )


def test_tpu_rule_sends_non_lowering_products_to_xla(monkeypatch):
    """On TPU, "pallas" narrows to the kernels above; the gather/scatter
    products and the walker run their XLA implementation, and the
    interpreter is refused."""
    monkeypatch.setattr(dispatch.jax, "default_backend", lambda: "tpu")
    assert dispatch.auto_backend() == "pallas"
    chosen = {p: dispatch.resolve(p, "pallas") for p in dispatch.PRODUCTS}
    assert chosen == {
        "phi_matvec": "xla", "phi_t_matvec": "xla", "khat_matvec": "xla",
        "walk_sample": "xla", "gram_block": "pallas",
        "woodbury_apply": "pallas",
    }
    assert dispatch.resolve("gram_block", "xla") == "xla"
    with pytest.raises(ValueError, match="pallas-interpret"):
        dispatch.resolve("gram_block", "pallas-interpret")
