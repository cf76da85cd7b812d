"""The experts' combine as one Mosaic kernel: ``moe.py::combine``'s form
on a TPU.

The grouped products leave the down product ``ys`` (T*k, H) float32 in
expert order. The combine gives each token the sum of its ``k`` rows,
each times its weight. The XLA form weights all ``T*k`` rows, gathers
them back into token order and sums each token's ``k``: three float32
passes over ``ys`` (136 MB a layer at 2,080 tokens of ``k`` 8). Here a
grid step takes a tile of tokens and fetches each of its pairs' rows
straight from HBM into VMEM, one DMA a row, then weights and sums them
there: ``ys`` is read once (a pair on another chip's experts not at
all) and the output written once. The next tile's rows are fetched
while this one is summed (two slots of VMEM).

A row of ``ys`` as a TPU lays it out is one sublane of each of ``H /
128`` tiles of 8 rows, 4 KB apart, and Mosaic slices a tiled array by
whole tiles only. So ``ys`` reaches the kernel as (row // 8, lane block,
row % 8, 1, 128), the same bytes (XLA passes a bitcast, not a copy), in
which a row is a slice of whole (1, 128) tiles: a strided DMA. The rows
land in a VMEM slot laid out as the output is, (token // 8, lane block,
token % 8, 128), so the sums run on whole vregs.

The mathematics and the precision are the XLA form's: each float32 row
times its float32 weight, added to 0.0 in the order ``j = 0 .. k-1``.
A pair of weight 0 (every pair on another chip's experts) adds 0, as
its zero-weighted row does there, whatever its slot holds. (On a TPU
XLA's reduction adds the ``k`` terms in another order, so the two forms
differ there in the last bits of a sum; on a CPU they agree bit for
bit.)

Every loop of the body whose trip count is a number of tokens, of pairs
or ``k`` is a ``lax.fori_loop``, and the index arithmetic is ``lax``'s
(``jnp``'s floor division and remainder each lower as a function of
their own): the Mosaic module is one size at every ``T`` and ``k``, and
tracing and lowering it take 0.1 to 0.2 s on a host CPU.
The caller's one ``jax.jit`` a shape makes the expert layers of a
program share that.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
TILE = 128                      # tokens a grid step
SUB = 8                         # rows summed at once (one sublane tile)
UNROLL = 4                      # DMA starts a loop step
WAIT_ROWS = 8                   # rows' bytes a wait
BUFFER_BYTES = 24 << 20         # both slots of fetched rows
VMEM_LIMIT = 64 * 1024 * 1024


def tile_for(t: int, k: int, h: int) -> int:
    """Tokens a grid step: :data:`TILE`, halved while two slots of ``k``
    rows a token pass :data:`BUFFER_BYTES`; never more than ``t``."""
    tile = TILE
    while tile > SUB and 2 * k * tile * h * 4 > BUFFER_BYTES:
        tile //= 2
    return min(tile, t)


def _bits(t: int, k: int, h: int) -> tuple[int, int]:
    """Bits of a pair's word that hold its place in its tile's slot, and
    of those the token's (:func:`combine`)."""
    tile_bits = max(tile_for(t, k, h) - 1, 1).bit_length()
    return tile_bits + max(k - 1, 1).bit_length(), tile_bits


def supported(t: int, k: int, h: int) -> bool:
    """Shapes Mosaic tiles: a row whole 128-lane blocks, the tokens
    whole sublane tiles, two slots of a tile's rows in VMEM, a pair's
    row and place in one int32."""
    return h % 128 == 0 and t % SUB == 0 and t > 0 \
        and 2 * k * tile_for(t, k, h) * h * 4 <= BUFFER_BYTES \
        and t * k < 1 << (31 - _bits(t, k, h)[0])


def _kernel(starts_ref, pairs_ref, w_ref, ys_hbm, o_ref, buf, sem, *,
            bits: int, tile_bits: int):
    i, n_tiles = pl.program_id(0), pl.num_programs(0)
    shr, mask = lax.shift_right_logical, lax.bitwise_and

    def start(p, slot):
        """Start the DMA of pair ``p`` (of ``pairs``) into ``slot``."""
        code = pairs_ref[p]
        row, to = shr(code, bits), mask(code, (1 << bits) - 1)
        j, r = shr(to, tile_bits), mask(to, (1 << tile_bits) - 1)
        # (x // 8, x % 8): the tile of 8 rows, the row in it
        pltpu.make_async_copy(
            ys_hbm.at[shr(row, 3), :, mask(row, 7)],
            buf.at[slot, j, shr(r, 3), :, pl.ds(mask(r, 7), 1)],
            sem.at[slot]).start()

    def fetch(at, carry):
        """Start the DMAs of tile ``at``'s pairs into its slot."""
        lo, hi = starts_ref[at], starts_ref[at + 1]
        slot = lax.rem(at, 2)

        def some(q, carry):
            for u in range(UNROLL):
                start(lo + q * UNROLL + u, slot)
            return carry

        def one(p, carry):
            start(p, slot)
            return carry
        whole = lax.div(hi - lo, UNROLL)
        lax.fori_loop(0, whole, some, 0)
        return lax.fori_loop(lo + whole * UNROLL, hi, one, carry)

    # the first step fetches its own tile and the next, every other step
    # the next: this one's rows arrived while the step before summed
    lax.fori_loop(lax.select(i == 0, 0, i + 1), lax.min(i + 2, n_tiles),
                  fetch, 0)
    slot = lax.rem(i, 2)

    # every copy is one row: a wait of WAIT_ROWS rows' bytes waits for
    # any WAIT_ROWS of them
    def wait(rows):
        def one(_, carry):
            at = buf.at[slot, 0, 0, :, pl.ds(0, rows)]
            pltpu.make_async_copy(at, at, sem.at[slot]).wait()
            return carry
        return one
    n = starts_ref[i + 1] - starts_ref[i]
    lax.fori_loop(0, lax.div(n, WAIT_ROWS), wait(WAIT_ROWS), 0)
    lax.fori_loop(0, lax.rem(n, WAIT_ROWS), wait(1), 0)

    def rows(g, carry):
        w_at = pl.ds(pl.multiple_of(g * SUB, SUB), SUB)

        def add(j, acc):
            w = w_ref[j, w_at, :][None]
            # a pair not fetched has weight 0: what its slot holds is
            # not added
            return acc + jnp.where(w != 0, buf[slot, j, g] * w, 0.0)
        o_ref[g] = lax.fori_loop(0, w_ref.shape[0], add,
                                 jnp.zeros(o_ref.shape[1:], F32))
        return carry
    lax.fori_loop(0, o_ref.shape[0], rows, 0)


def combine(ys: jax.Array, pos: jax.Array, weights: jax.Array,
            mine: jax.Array | None = None, *,
            interpret: bool = False) -> jax.Array:
    """``ys`` (T*k, H) float32 in expert order, ``pos`` (T, k) int32 the
    row of each token's pair in it, ``weights`` (T, k) float32 in token
    order, ``mine`` (T, k) bool the pairs to fetch (``None``: all; the
    weight of any other is 0). Returns (T, H) float32: ``sum_j weights[t,
    j] * ys[pos[t, j]]``, ``j`` in order."""
    t, k = pos.shape
    n, h = ys.shape
    lanes = h // 128
    tile = tile_for(t, k, h)
    n_tiles = pl.cdiv(t, tile)
    # The pairs to fetch, token by token, as one word each: the row in
    # ``ys`` above, the pair's place in its tile's slot (j, token % tile)
    # below. ``starts[i]``: tile ``i``'s first.
    bits, tile_bits = _bits(t, k, h)
    tok = jnp.arange(t, dtype=jnp.int32)[:, None]
    to = jnp.arange(k, dtype=jnp.int32)[None] << tile_bits | tok % tile
    pairs = (pos << bits | to).reshape(-1)
    ends = np.minimum(np.arange(n_tiles + 1) * tile, t) * k
    if mine is None:
        starts = jnp.asarray(ends, jnp.int32)
    else:   # the pairs to fetch first, in order; the others left out
        at = jnp.cumsum(mine.reshape(-1), dtype=jnp.int32)
        pairs = jnp.zeros_like(pairs).at[
            jnp.where(mine.reshape(-1), at - 1, t * k)].set(
            pairs, mode="drop")
        starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), at])[ends]
    # ``ys`` and the output as they lie (module docstring): bitcasts
    ys = ys.reshape(n // SUB, SUB, lanes, 128).transpose(0, 2, 1, 3)
    # a token's weights as a column each: (k, T, 1), a pair's a sublane
    w = weights.astype(F32).T[:, :, None]
    out = pl.pallas_call(
        functools.partial(_kernel, bits=bits, tile_bits=tile_bits),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n_tiles,),
            in_specs=[pl.BlockSpec((k, tile, 1), lambda i, *_: (0, i, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tile // SUB, lanes, SUB, 128),
                                   lambda i, *_: (i, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, k, tile // SUB, lanes, SUB, 128), F32),
                pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((t // SUB, lanes, SUB, 128), F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="lm_moe_combine",
    )(starts, pairs, w, ys.reshape(n // SUB, lanes, SUB, 1, 128))
    return out.transpose(0, 2, 1, 3).reshape(t, h)
