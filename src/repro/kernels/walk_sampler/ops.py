"""Backend entry points for the walk sampler (mirrors ell_spmv/ops.py).

No custom VJP is needed: sampling produces the integer/load structure of the
trace, which downstream code treats as data — differentiability w.r.t. the
modulation vector ``f`` lives entirely in ``feature_values`` (core/features).
"""
from __future__ import annotations

from .ref import walk_sample_ref
from .walk_sampler import walk_sample as _walk_sample_kernel


def walk_sample_xla(
    walk_table, deg, nodes, seed,
    *, n_walkers, p_halt, l_max, reweight=True, scheme="iid",
):
    return walk_sample_ref(
        walk_table, deg, nodes, seed,
        n_walkers=n_walkers, p_halt=p_halt, l_max=l_max, reweight=reweight,
        scheme=scheme,
    )


def walk_sample_pallas(
    walk_table, deg, nodes, seed,
    *, n_walkers, p_halt, l_max, reweight=True, scheme="iid", interpret=False,
):
    return _walk_sample_kernel(
        walk_table, deg, nodes, seed,
        n_walkers=n_walkers, p_halt=p_halt, l_max=l_max, reweight=reweight,
        scheme=scheme, interpret=interpret,
    )
