"""16-bit LFSR + two-layer swapper selection network (paper Fig. 10).

The hardware drives every CLT-GRNG cell in a tile from ONE 16-bit LFSR
through two layers of wire swappers.  A fixed input vector containing
exactly eight 1s is permuted by the swappers, so exactly 8 of the 16
FeFETs are enabled each cycle regardless of the LFSR state.

  * layer 1: swap adjacent bits (2n, 2n+1) when control c1[n] is set
  * layer 2: swap bit n with bit n+8 when control c2[n] is set
  * controls: low 8 LFSR bits -> layer 1, high 8 bits -> layer 2

We use the alternating fixed input [1,0,1,0,...] so that layer 1 is
meaningful (each adjacent pair holds exactly one 1; with the all-ones-
first layout layer 1 would be a no-op).  The permutation network
preserves the multiset, so the exactly-8-selected invariant holds by
construction — property-tested in tests/test_lfsr.py.

The LFSR is a Galois-form maximal-length x^16+x^14+x^13+x^11+1
(feedback mask 0xB400), period 65535 for any nonzero seed.

Note on reachability: the two swapper layers can reach at most 2^16
selection patterns, a structured subset of the C(16,8)=12870 possible
8-of-16 subsets.  ``enumerate_reachable()`` measures the actual count —
this is an analysis the paper does not report, surfaced in
benchmarks/fig10_selection.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.hashing import mix32

LFSR_MASK = 0xB400  # taps 16,14,13,11 (maximal length)
FIXED_INPUT = tuple([1, 0] * 8)  # eight 1s, alternating


def lfsr_next(state: jnp.ndarray) -> jnp.ndarray:
    """One Galois LFSR step. ``state`` is uint32 holding a 16-bit value."""
    state = jnp.asarray(state, jnp.uint32)
    lsb = state & jnp.uint32(1)
    shifted = state >> jnp.uint32(1)
    return jnp.where(lsb == 1, shifted ^ jnp.uint32(LFSR_MASK), shifted)


@functools.partial(jax.jit, static_argnames=("num",))
def lfsr_states(seed: int | jnp.ndarray, num: int) -> jnp.ndarray:
    """Generate ``num`` successive LFSR states from ``seed``. -> [num] u32.

    Jitted so that a call outside a trace reuses one executable per
    ``num`` (an eager ``lax.scan`` recompiles on every call)."""
    seed = jnp.asarray(seed, jnp.uint32) & jnp.uint32(0xFFFF)
    seed = jnp.where(seed == 0, jnp.uint32(0xACE1), seed)  # 0 is a fixed point

    def step(s, _):
        nxt = lfsr_next(s)
        return nxt, s

    _, states = lax.scan(step, seed, None, length=num)
    return states


def swapper_select(state: jnp.ndarray) -> jnp.ndarray:
    """Map LFSR state(s) -> selection vector(s) in {0,1}^16, exactly 8 ones.

    ``state``: uint32 array of any shape S. Returns float32 [*S, 16].
    Pure arithmetic (no gathers) so it vectorizes on the VPU and is
    reproduced verbatim inside the Pallas kernels.
    """
    state = jnp.asarray(state, jnp.uint32)
    c1 = ((state[..., None] >> jnp.arange(8, dtype=jnp.uint32)) & 1).astype(
        jnp.float32
    )  # [*S, 8]
    c2 = ((state[..., None] >> (8 + jnp.arange(8, dtype=jnp.uint32))) & 1).astype(
        jnp.float32
    )  # [*S, 8]

    v = jnp.asarray(FIXED_INPUT, jnp.float32)
    v = jnp.broadcast_to(v, state.shape + (16,))

    # Layer 1: swap within adjacent pairs (2n, 2n+1).
    pairs = v.reshape(state.shape + (8, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    a1 = a + c1 * (b - a)
    b1 = b + c1 * (a - b)
    v1 = jnp.stack([a1, b1], axis=-1).reshape(state.shape + (16,))

    # Layer 2: swap bit n with bit n+8.
    lo, hi = v1[..., :8], v1[..., 8:]
    lo2 = lo + c2 * (hi - lo)
    hi2 = hi + c2 * (lo - hi)
    return jnp.concatenate([lo2, hi2], axis=-1)


def selection_stream(seed: int, num: int) -> jnp.ndarray:
    """``num`` successive selection vectors. -> float32 [num, 16]."""
    return swapper_select(lfsr_states(seed, num))


def tile_seeds(base_seed: int, n_tiles: int) -> jnp.ndarray:
    """Derive per-tile LFSR seeds (hardware: per-macro selector instances)."""
    h = mix32(jnp.arange(n_tiles, dtype=jnp.uint32) * jnp.uint32(0x9E3779B9)
              + jnp.uint32(base_seed))
    s = h & jnp.uint32(0xFFFF)
    return jnp.where(s == 0, jnp.uint32(0xACE1), s)


def cell_selections(rows: jnp.ndarray, cols: jnp.ndarray, r, seed) -> jnp.ndarray:
    """Idealized per-cell independent selections (granularity='cell').

    Uses the swapper network with a hash-derived per-(cell, sample) state,
    so the exactly-8 invariant still holds but cells are decorrelated.
    rows/cols broadcast; returns float32 [..., 16].
    """
    from repro.core.hashing import hash3  # local import to avoid cycle

    h = hash3(rows, cols, jnp.asarray(r, jnp.uint32), seed)
    s = h & jnp.uint32(0xFFFF)
    s = jnp.where(s == 0, jnp.uint32(0xACE1), s)
    return swapper_select(s)


def indexed_states(seed, idx: jnp.ndarray) -> jnp.ndarray:
    """Random-access selection states for traced sample indices.

    The hardware streams its LFSR sequentially; for decode loops with a
    *traced* position we need O(1) random access into an equivalent
    stream.  We hash the sample index into a 16-bit state and reuse the
    same swapper network — still write-free, still exactly-8-of-16.
    """
    h = mix32(jnp.asarray(idx, jnp.uint32) * jnp.uint32(0x9E3779B9)
              + jnp.uint32(seed))
    s = h & jnp.uint32(0xFFFF)
    return jnp.where(s == 0, jnp.uint32(0xACE1), s)


def indexed_selections(seed, idx: jnp.ndarray) -> jnp.ndarray:
    """Selection vectors for arbitrary (traced) sample indices. [*,16]."""
    return swapper_select(indexed_states(seed, idx))


def enumerate_reachable() -> tuple[int, jnp.ndarray]:
    """Count distinct selection patterns over all 2^16 LFSR states.

    Returns (count, per-position selection frequency [16]).
    """
    states = jnp.arange(1, 1 << 16, dtype=jnp.uint32)
    sels = swapper_select(states)  # [65535, 16]
    codes = (sels.astype(jnp.uint32) * (jnp.uint32(1) << jnp.arange(16, dtype=jnp.uint32))).sum(
        axis=-1
    )
    count = int(jnp.unique(codes).shape[0])
    freq = sels.mean(axis=0)
    return count, freq
