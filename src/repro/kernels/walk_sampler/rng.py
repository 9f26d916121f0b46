"""Counter-based RNG shared by the walk-sampler kernel and its oracle.

The sampler needs a random stream addressed by the *logical* coordinate of
each draw — (seed, start node, walker, step, salt) — rather than by a
stateful key that is split as the computation is laid out.  Two reasons
(DESIGN.md §3.6):

  * Chunked == monolithic: a node's walks depend only on its absolute node
    id, so sampling nodes [0, N) in one shot or in 65536-row chunks yields
    bit-identical WalkTraces, and Φ-row subsets (training nodes, shards)
    are consistent with the full Φ by construction.
  * Kernel == oracle: the hash is plain uint32 arithmetic, so the Pallas
    kernel and the jnp oracle draw identical uniforms and produce identical
    walk *structure* (cols/lens bit-exact; the float load chains match to
    FMA-contraction ulps across compilations).

The generator is a murmur3-style chain: each coordinate word is folded in
with a distinct odd multiplier and the fmix32 finalizer (a bijection on
uint32, the avalanche core of murmur3/splitmix).  This is not crypto — it
is a decorrelation hash with good equidistribution for Monte-Carlo use,
the same trade Philox/Threefry-lite samplers make.

Variance-reduced walker schemes (DESIGN.md §3.9) are driven from the same
counter chain, so every scheme keeps the chunked==monolithic and
subset-row invariances for free:

  * ``"iid"``        independent uniforms per (node, walker, step) — the
                     original stream, bit-for-bit.
  * ``"antithetic"`` walkers (2k, 2k+1) share the even partner's halt
                     stream; the odd walker sees the mirrored uniform
                     1−u, so their termination events are maximally
                     negatively correlated (QMC-GRFs, PAPERS.md).
  * ``"qmc"``        per (node, step), the n_walkers halt uniforms are a
                     digitally-shifted van der Corput set: bit-reversed
                     walker index XOR a counter-hash shift keyed on
                     (seed, node, step) — a low-discrepancy point set per
                     draw coordinate, freshly scrambled by the same
                     fmix32 chain.
  * ``"grfspp"``     no halt stream at all — termination is integrated
                     out analytically at the deposit stage (ref.py).

Only the *halt* stream is scheme-dependent; directional choices stay iid,
so for every scheme the walk structure per walker is drawn from the same
law (and for ``"grfspp"`` it is bit-identical to ``"iid"``).
"""
from __future__ import annotations

import jax.numpy as jnp

SCHEMES = ("iid", "antithetic", "qmc", "grfspp")

_GOLDEN = 0x9E3779B9
_M1 = 0x85EBCA6B
_M2 = 0xC2B2AE35
_M3 = 0x27D4EB2F
# Walker-slot salt for the QMC digital shift: keys the per-(node, step)
# scramble on a coordinate no real walker id ever takes.
_QMC_SALT = 0xFFFFFFFF

_INV_2_24 = float(2.0**-24)


def _u32(x) -> jnp.ndarray:
    return jnp.asarray(x).astype(jnp.uint32)


def fmix32(h: jnp.ndarray) -> jnp.ndarray:
    """murmur3 finalizer — bijective avalanche mix on uint32."""
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(_M1)
    h = h ^ (h >> jnp.uint32(13))
    h = h * jnp.uint32(_M2)
    h = h ^ (h >> jnp.uint32(16))
    return h


def counter_bits(seed, node, walker, ctr) -> jnp.ndarray:
    """uint32 hash of the draw coordinate (broadcasts over array args)."""
    h = _u32(seed) ^ jnp.uint32(_GOLDEN)
    h = fmix32(h ^ (_u32(node) * jnp.uint32(_M1)))
    h = fmix32(h ^ (_u32(walker) * jnp.uint32(_M2)))
    h = fmix32(h ^ (_u32(ctr) * jnp.uint32(_M3)))
    return h


def counter_uniform(seed, node, walker, ctr) -> jnp.ndarray:
    """f32 uniform in [0, 1) from the top 24 bits of the counter hash."""
    return _top24_uniform(counter_bits(seed, node, walker, ctr))


def _top24_uniform(bits: jnp.ndarray) -> jnp.ndarray:
    """f32 in [0, 1) from the top 24 bits of a uint32.  The shifted value
    fits in 24 bits, so the hop through int32 is exact; Mosaic has no
    uint32 → float32 cast."""
    top = (bits >> jnp.uint32(8)).astype(jnp.int32)
    return top.astype(jnp.float32) * jnp.float32(_INV_2_24)


def bitrev32(x: jnp.ndarray) -> jnp.ndarray:
    """Bit-reversal on uint32 — the base-2 radical inverse times 2³²."""
    x = _u32(x)
    x = ((x & jnp.uint32(0x55555555)) << jnp.uint32(1)) | (
        (x >> jnp.uint32(1)) & jnp.uint32(0x55555555))
    x = ((x & jnp.uint32(0x33333333)) << jnp.uint32(2)) | (
        (x >> jnp.uint32(2)) & jnp.uint32(0x33333333))
    x = ((x & jnp.uint32(0x0F0F0F0F)) << jnp.uint32(4)) | (
        (x >> jnp.uint32(4)) & jnp.uint32(0x0F0F0F0F))
    x = ((x & jnp.uint32(0x00FF00FF)) << jnp.uint32(8)) | (
        (x >> jnp.uint32(8)) & jnp.uint32(0x00FF00FF))
    return (x << jnp.uint32(16)) | (x >> jnp.uint32(16))


def halt_uniform(seed, node, walker, ctr, *, scheme: str) -> jnp.ndarray:
    """Scheme-dependent f32 uniform driving walk *termination*.

    All schemes are keyed on the same (seed, node, walker, ctr) coordinate,
    so chunked / sharded / subset sampling stay bit-identical per row.
    ``walker`` may be an array (broadcasts, as counter_uniform)."""
    if scheme in ("iid", "grfspp"):
        return counter_uniform(seed, node, walker, ctr)
    if scheme == "antithetic":
        # Pairs (2k, 2k+1) read the even partner's stream; the odd walker
        # mirrors it.  1−u ∈ (0, 1] — the halt test u ≥ p_halt is closed
        # below, so the mirrored stream never changes the event's support.
        partner = _u32(walker) & jnp.uint32(0xFFFFFFFE)
        u = counter_uniform(seed, node, partner, ctr)
        odd = (_u32(walker) & jnp.uint32(1)) == jnp.uint32(1)
        return jnp.where(odd, jnp.float32(1.0) - u, u)
    if scheme == "qmc":
        # Digitally-shifted van der Corput: per (node, ctr) the walkers'
        # uniforms form one low-discrepancy point set, scrambled by an
        # XOR shift from the counter chain (Owen-style digital shift).
        shift = counter_bits(seed, node, jnp.uint32(_QMC_SALT), ctr)
        bits = bitrev32(walker) ^ shift
        return _top24_uniform(bits)
    raise ValueError(f"unknown walk scheme {scheme!r}; valid: {SCHEMES}")
