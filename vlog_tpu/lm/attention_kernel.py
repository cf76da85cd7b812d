"""The kernel forms of ``model.py``'s attention over paged pools and of
its sparse attention's choice of keys: five Mosaic kernels, each the
loop's mathematics at the loop's precision.

**The chunk** (:func:`chunk_attention`, ``paged_attention``'s form for a
prefill chunk). One sequence, ``Q`` consecutive query positions, keys
read straight from the paged pools through the page table. A tile's
scores, its running maximum, sum and accumulator live in VMEM: nothing
of ``queries x keys`` size crosses HBM. The mathematics and the
precision are the loop's (``paged_attention``): bfloat16 ``q`` (already
scaled), K and V; float32 scores, mask, online softmax and accumulator,
one softmax step a block of ``block_pages`` pages; ``p`` rounded to
bfloat16 for the value product.

The grid is ``(query tiles, key blocks)``, the key blocks innermost and
as many as the chunk's last query reads (a dynamic bound). A tile is
``Q_TILE`` queries of ALL heads, folded head-major into rows (a kv
head's ``(g * Q_TILE, hd)`` against its ``(keys, hd)``), so a K/V block
is fetched once for every head. A page reaches the kernel as the pool
holds it: ``(pages, page, nkv, hd)`` is, byte for byte, ``(pages * page
* nkv, hd)`` with rows by position then kv head, two kv heads to a
32-bit word (XLA passes the pool as a bitcast, never a copy); a pair of
heads is read with a sublane stride and split in VMEM. A key block that
no query of a tile can see (above the diagonal, outside the band, past
the last live page) does no work, and its index map stays on the block
before it, so it fetches nothing either. A block that every query of
the tile sees whole skips the positional mask. A head of 256 lies in
the pool as two 128-lane halves (``model.py::kv_tail``), so a page is
still ``(position x kv head x half, 128)`` as it lies; a word then pairs
a head's two halves, which are split and set side by side in VMEM.

Sized on the chip (PERF.md section 6, PR 34): ``Q_TILE`` 128 with the
geometry's block of 4 pages reads 4.7 ms for 2,048 queries over 18k keys
(64: 5.1 ms; 256 spills: 7.9 ms; the loop: 23.0 ms).

**The rows** (:func:`rows_attention`, ``paged_attention``'s form for
``S`` sequences of one query each over the same K/V pools). The grid is
ONE axis with one step a block that some row reads (a dynamic bound):
the rows in order, each row's blocks of ``block_pages`` pages from its
band's first (the table of a window layer is a ring from ``base`` on) to
its last live one. The plan of it (:func:`_rows_plan`: each step's row
and block) is made beside the call from the rows' positions and is
scalar-prefetched with the tables, so an index map is three reads and
every page of the next step, the next ROW's first among them, is
fetched while this one computes. A step reads its block of K and of V
and takes one softmax step over it, as the loop does; a row's first
step clears the running maximum, sum and accumulator, its last writes
the row out. So a step of the engine costs the SUM of its rows' visible
blocks and not ``rows`` times the longest (the loop's), and no grid
step is spent on a block that is not read (a grid of ``rows x the
longest row's blocks`` with the unread ones skipped cost 1.0 us a
skipped step in index arithmetic, 0.8 of a full layer's 1.77 ms:
PERF.md section 6, PR 36). An absent row keeps one step, reads
nothing and comes out zeros. The kv heads are NOT split: a page goes to
the matrix unit as the pool holds it, ``(position x kv head, 128)``,
every query head is run against every row of it and keeps its own kv
head's by the mask. That is ``nkv`` times the score and ``exp`` work of
a split, on a kernel that waits on HBM (a key byte meets 32 operations
where the chip has 240 to spend), and nothing of K or V is moved inside
VMEM.

**Latent attention's chunk** (:func:`latent_chunk_attention`, the
expanded form of ``model.py::expanded_attention``) is a second kernel
over the same pages: the pool holds one latent ``(c | k_rope)`` a
position, and a head's keys and values are products of it. The grid is
``(heads, key blocks)``, the key blocks innermost; a step reads a block
of latents through the page table, expands it for ITS head in VMEM
(``k = c Wuk[h]``, ``v = c Wuv[h]``: once for all of the chunk's
queries, nothing expanded crosses HBM) and runs the chunk's queries
against it in sub-tiles of ``LATENT_Q_TILE`` under the same online
softmax, the rope part of the score as a second product against the
block's own ``k_rope`` lanes. A latent is read once a head; the
mathematics and the precision are the loop's.

**Latent attention's rows** (:func:`latent_rows_attention`, the
absorbed form of ``model.py::absorbed_attention``): one query a
sequence, every head's query already carried into the latent's space,
so the latent is ONE key head whose first lanes are also the value. The
grid is ``(rows, key blocks)``; a step reads a block of ONE row's pages
through that row's table and runs the row's 32 heads against it (scores
and the value product against the same bytes); a row's blocks past its
last page neither compute nor fetch, so a step's work is the sum of the
rows' contexts and not 32 times the longest (the loop's).

**The choice of keys** (:func:`select_keys_kernel`, ``model.py::
select_keys`` for a prefill chunk): each query keeps its ``top`` best
index scores, exactly, ties to the lower position. The grid is ``(query
tiles, key blocks)``, the key blocks innermost and as many as the
chunk's last query reads (a dynamic bound); past a tile's last causal
block its index map stays on that block, so it fetches nothing. A step
turns its ``(SELECT_Q_TILE, block)`` float32 scores into int32 keys
whose signed order is the scores' order and keeps them in a ``(blocks,
SELECT_Q_TILE, block)`` VMEM scratch: the scores cross HBM once, and
nothing else does but the mask. At the tile's last step the search runs
over the scratch for all of the tile's queries together (a pass ends
in a reduction the next one waits for, so the tile runs one chain of
passes, not one a group of 32 queries): the k-th key one bit a pass, 32
passes (a pass compares every live key of the tile against its query's
candidate and counts, lane-wise partial sums reduced once a pass),
then, only in a tile where ties at the k-th straddle the cut, a
bisection over the position (16 passes at 40,960 keys) that keeps the
lower-positioned ties; then the tile's mask is written as the chunk's
kernel reads it, int8 ``(queries, keys)`` keys-minor, zeros past the
tile's live blocks.

Sized on the chip (PERF.md, section 6): four groups of 32 queries
searched one after another read 2.1 ms a layer at 18k keys (the loop:
19.8), 1.1 ms of it fixed, the chains of dependent passes; two bits a
pass (three compares a key) read slower past 8k keys, where the vector
unit and not the loads bounds a pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
BF16 = jnp.bfloat16
MASKED = -1e30
Q_TILE = 128                    # queries a tile (x g heads = its rows)
LATENT_Q_TILE = 256             # queries a sub-tile of the latent kernel
VMEM_LIMIT = 64 * 1024 * 1024


HEAD_DIMS = (128, 256)           # a head one or two 128-lane blocks


def supported(nq: int, nkv: int, hd: int, page: int) -> bool:
    """Shapes Mosaic tiles: a head is one or two 128-lane blocks (a page
    is then read as the pool holds it, two kv heads a 32-bit word), a
    page whole int8 mask tiles, the chunk whole query tiles."""
    tq = min(nq, Q_TILE)
    return hd in HEAD_DIMS and nkv % 2 == 0 and page % 32 == 0 \
        and nq % tq == 0 and tq % 32 == 0


def _pages_seen(meta, qi, *, tq: int, page: int, window: int | None):
    """``[lo, hi)``: the table slots that hold a key some query of tile
    ``qi`` attends (causal, the band, live pages)."""
    p0, n_pages, base = meta[0], meta[1], meta[2]
    q_lo = p0 + qi * tq
    hi = jnp.minimum(n_pages, (q_lo + tq - 1 - base) // page + 1)
    lo = 0 if window is None else \
        jnp.maximum(q_lo - window + 1 - base, 0) // page
    return lo, hi


def _halves(words: jax.Array) -> tuple[jax.Array, jax.Array]:
    """The two bfloat16 rows a 32-bit word of a page holds: the even row
    in the low half, the odd in the high."""
    return tuple(pltpu.bitcast(w, F32).astype(BF16)
                 for w in (words << 16, words & jnp.uint32(0xFFFF0000)))


def _kernel(table, meta, q_ref, *refs, tq: int, nkv: int, g: int, hd: int,
            page: int, bp: int, window: int | None, masked: bool,
            split: int):
    del table
    k_refs, v_refs = refs[:bp], refs[bp:2 * bp]
    refs = refs[2 * bp:]
    chosen_ref = refs[0] if masked else None
    o_ref, m_s, l_s, acc_s = refs[-4:]
    qi, kb = pl.program_id(0), pl.program_id(1)
    keys, rows = bp * page, g * tq
    p0, n_pages, base = meta[0], meta[1], meta[2]
    q_lo = p0 + qi * tq
    lo, hi = _pages_seen(meta, qi, tq=tq, page=page, window=window)

    @pl.when(kb == 0)
    def _():
        m_s[...] = jnp.full(m_s.shape, MASKED, F32)
        l_s[...] = jnp.zeros(l_s.shape, F32)
        acc_s[...] = jnp.zeros(acc_s.shape, F32)

    k_lo = base + kb * keys

    def heads(page_refs):
        """A block's pages ``(page * rows a position, lanes)``, rows by
        position then kv head (then half), as one ``(keys, hd)`` array a
        kv head. Two rows share a 32-bit word, so a pair is read with a
        sublane stride and split: two kv heads or, for a head of two
        halves, one head's halves, set side by side."""
        stride = nkv * split // 2
        out = []
        for pair in range(stride):
            words = jnp.concatenate(
                [r.bitcast(jnp.uint32)[pl.ds(pair, page, stride=stride), :]
                 for r in page_refs], axis=0)
            low, high = _halves(words)
            out += [jnp.concatenate([low, high], axis=1)] if split == 2 \
                else [low, high]
        return out

    def tile(edge: bool):
        ok = None
        if edge:
            qpos = q_lo + lax.broadcasted_iota(jnp.int32, (tq, keys), 0)
            kpos = k_lo + lax.broadcasted_iota(jnp.int32, (tq, keys), 1)
            ok = (kpos <= qpos) & (kpos < base + n_pages * page)
            if window is not None:
                ok &= kpos > qpos - window
        if masked:
            picked = chosen_ref[...].astype(jnp.int32) != 0
            ok = picked if ok is None else ok & picked
        for h, (k, v) in enumerate(zip(heads(k_refs), heads(v_refs))):
            at = pl.ds(h * rows, rows)
            q = q_ref[h * g:(h + 1) * g].reshape(rows, hd)
            s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32)  # (rows, keys)
            if ok is not None:
                s = jnp.where(ok[None], s.reshape(g, tq, keys),
                              MASKED).reshape(rows, keys)
            m_prev = m_s[at]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            # a masked score underflows to 0 against any real maximum; a
            # row with no key yet (m_new MASKED) is wiped by the first
            # real one's scale, or zeroed at the end
            p = jnp.exp(s - m_new)
            scale = jnp.exp(m_prev - m_new)
            l_s[at] = l_s[at] * scale + jnp.sum(p, axis=1, keepdims=True)
            acc_s[at] = acc_s[at] * scale + jnp.dot(
                p.astype(BF16), v, preferred_element_type=F32)
            m_s[at] = m_new

    seen = (kb * bp < hi) & ((kb + 1) * bp > lo)
    whole = (k_lo + keys - 1 <= q_lo) & ((kb + 1) * bp <= n_pages)
    if window is not None:
        whole &= k_lo > q_lo + tq - 1 - window
    pl.when(seen & whole)(functools.partial(tile, False))
    pl.when(seen & jnp.logical_not(whole))(functools.partial(tile, True))

    @pl.when(kb == pl.num_programs(1) - 1)
    def _():
        out = jnp.where(m_s[...] > 0.5 * MASKED,
                        acc_s[...] / jnp.maximum(l_s[...], 1e-30), 0.0)
        o_ref[...] = out.reshape(nkv * g, tq, hd)


def chunk_attention(q: jax.Array, p0: jax.Array, n_pages: jax.Array,
                    pool_k: jax.Array, pool_v: jax.Array, table: jax.Array,
                    base: jax.Array, *, window: int | None, page: int,
                    block_pages: int, chosen: jax.Array | None = None,
                    q_tile: int = Q_TILE, interpret: bool = False
                    ) -> jax.Array:
    """``q`` (Q, nkv, g, hd) bfloat16, scaled, at positions ``p0 +
    arange(Q)``; the pools ``(pages, page, nkv, hd)`` (or, a head in
    128-lane halves, ``(pages, page, 2 nkv, 128)``); ``table`` (W,)
    physical pages from position ``base`` () on, the first ``n_pages`` ()
    of them live; ``chosen`` (Q, keys) bool where given
    (``paged_attention``). Returns (Q, nkv, g, hd) float32."""
    nq, nkv, g, hd = q.shape
    tq, bp, width = min(nq, q_tile), block_pages, table.shape[0]
    keys = bp * page
    lanes = pool_k.shape[-1]        # hd, or 128 for a head of two halves
    rows_a_position = nkv * hd // lanes
    meta = jnp.stack([p0, n_pages, base]).astype(jnp.int32)
    # as many key blocks as the last query reads; the interpreter takes
    # no dynamic bound: there the steps past them are skipped one by one
    n_blocks = -(-width // bp) if interpret else \
        jnp.maximum((n_pages + bp - 1) // bp, 1)
    seen = functools.partial(_pages_seen, tq=tq, page=page, window=window)

    def block(kb, qi, meta):
        """The key block tile ``qi`` holds at grid step ``kb``: its own
        where it reads one, else the nearest it does read (no fetch)."""
        lo, hi = seen(meta, qi)
        return jnp.clip(kb, lo // bp, jnp.maximum(hi - 1, 0) // bp)

    def kv_spec(j):
        def index(qi, kb, table, meta):
            slot = jnp.clip(block(kb, qi, meta) * bp + j, 0,
                            jnp.minimum(jnp.maximum(meta[1], 1), width) - 1)
            return table[slot], 0
        return pl.BlockSpec((page * rows_a_position, lanes), index)

    # the heads lead, so a tile's rows are head-major; a page's rows are
    # (position, kv head): the pool's own bytes, no relayout
    by_head = pl.BlockSpec((nkv * g, tq, hd), lambda qi, kb, *_: (0, qi, 0))
    in_specs = [by_head] + [kv_spec(j) for _ in "kv" for j in range(bp)]
    args = [q.reshape(nq, nkv * g, hd).transpose(1, 0, 2)]
    args += [pool_k.reshape(-1, lanes)] * bp \
        + [pool_v.reshape(-1, lanes)] * bp
    if chosen is not None:
        in_specs.append(pl.BlockSpec(
            (tq, keys), lambda qi, kb, table, meta:
            (qi, block(kb, qi, meta))))
        args.append(chosen.astype(jnp.int8))
    rows = nkv * g * tq
    out = pl.pallas_call(
        functools.partial(_kernel, tq=tq, nkv=nkv, g=g, hd=hd, page=page,
                          bp=bp, window=window, masked=chosen is not None,
                          split=hd // lanes),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(nq // tq, n_blocks),
            in_specs=in_specs, out_specs=by_head,
            scratch_shapes=[pltpu.VMEM((rows, 1), F32),
                            pltpu.VMEM((rows, 1), F32),
                            pltpu.VMEM((rows, hd), F32)]),
        out_shape=jax.ShapeDtypeStruct((nkv * g, nq, hd), F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="lm_chunk_attention",
    )(table.astype(jnp.int32), meta, *args)
    return out.transpose(1, 0, 2).reshape(nq, nkv, g, hd)


# --------------------------------------------------------------------------
# K/V pages: the rows, each over its own pages
# --------------------------------------------------------------------------

FAR = 1 << 30       # a key position no query reaches


def rows_supported(nkv: int, g: int, hd: int, page: int) -> bool:
    """Shapes Mosaic tiles: a head is one or two 128-lane blocks and the
    kv heads pair up in 32-bit words (a page is then read as the pool
    holds it, as :func:`supported` asks), the query heads whole bfloat16
    sublane tiles of the score product's rows, a page's rows whole lane
    blocks of the scores."""
    return hd in HEAD_DIMS and nkv % 2 == 0 and (nkv * g) % 16 == 0 \
        and page % 128 == 0


def _divmod(x: jax.Array, n: int) -> tuple[jax.Array, jax.Array]:
    """``divmod`` of non-negative int32 by a static ``n``: shifts where
    ``n`` is a power of two (the vector unit has no divider)."""
    if n & (n - 1) == 0:
        return x >> (n.bit_length() - 1), x & (n - 1)
    return lax.div(x, jnp.int32(n)), lax.rem(x, jnp.int32(n))


def _row_pages(qpos, last_pos, base, *, page: int, window: int | None):
    """``[lo, hi)``: the table slots that hold a key the query at
    ``qpos`` attends (causal, the band, live pages), for one row or for
    all of them."""
    n_pages = jnp.where(last_pos >= 0, (last_pos - base) // page + 1, 0)
    hi = jnp.clip((qpos - base) // page + 1, 0, n_pages)
    lo = jnp.zeros_like(hi) if window is None else \
        jnp.maximum(qpos - window + 1 - base, 0) // page
    return lo, hi


def _rows_plan(qpos, last_pos, base, *, width: int, page: int, bp: int,
               window: int | None):
    """The kernel's grid, one step a block that some row reads: ``(row |
    block | top | total)`` int32, the first three ``T = rows x blocks a
    table of ``width`` slots holds`` long. Grid step ``t`` serves table
    block ``block[t]`` of row ``row[t]``, whose last slot in sight is
    ``top[t]`` (a slot of the block past it reads that page again): the
    rows in order, each row's blocks from its band's first to its last
    live one. ``total`` steps are real, the rest repeat the last. A row
    with nothing to read keeps ONE step, in which it writes its zeros."""
    rows = qpos.shape[0]
    lo, hi = _row_pages(qpos, last_pos, base, page=page, window=window)
    first = lo // bp
    n = jnp.maximum(jnp.where(hi > 0, (hi - 1) // bp - first + 1, 0), 1)
    ends = jnp.cumsum(n)
    t = jnp.arange(rows * -(-width // bp), dtype=jnp.int32)
    # the row of step t: how many rows end at or before it (a few
    # compares a step; a binary search would be a loop on the device)
    row = jnp.minimum(jnp.sum(t[:, None] >= ends[None, :], axis=1), rows - 1)
    first, start, n, top = jnp.stack(
        [first, ends - n, n, jnp.maximum(hi, 1) - 1], axis=1)[row].T
    block = first + jnp.minimum(t - start, n - 1)
    return jnp.concatenate([row, block, top, ends[-1:]]).astype(jnp.int32)


def _paged_rows_kernel(steps, table, meta, q_ref, *refs, n_steps: int,
                       rows: int, nkv: int, g: int, page: int, bp: int,
                       window: int | None, split: int):
    del table
    k_refs, v_refs = refs[:bp], refs[bp:2 * bp]
    o_ref, m_s, l_s, acc_s = refs[2 * bp:]
    t = pl.program_id(0)
    r, kb, total = steps[t], steps[n_steps + t], steps[3 * n_steps]
    qpos, base = meta[r], meta[2 * rows + r]
    _, hi = _row_pages(qpos, meta[rows + r], base, page=page, window=window)

    @pl.when((t == 0) | (steps[jnp.maximum(t - 1, 0)] != r))
    def _():
        m_s[...] = jnp.full(m_s.shape, MASKED, F32)
        l_s[...] = jnp.zeros(l_s.shape, F32)
        acc_s[...] = jnp.zeros(acc_s.shape, F32)

    # every real step reads a block in its row's sight, but for the one
    # step of a row with nothing to read
    @pl.when((t < total) & (hi > 0))
    def _():
        # a page as the pool holds it: rows by position then kv head.
        # Every query head is run against every row and keeps its own kv
        # head's (the others' masked), so no key or value is moved
        # inside VMEM
        shape = (nkv * g, page * nkv)
        rel, head = _divmod(lax.broadcasted_iota(jnp.int32, shape, 1), nkv)
        mine, _ = _divmod(lax.broadcasted_iota(jnp.int32, shape, 0), g)
        rel = jnp.where(head == mine, rel, FAR)     # position in the page
        q = q_ref[...]

        def page_rows(ref):
            """``(page * nkv, hd)``: a head of two halves has them in
            one word, set side by side."""
            if split == 1:
                return ref[...]
            return jnp.concatenate(_halves(ref.bitcast(jnp.uint32)[...]),
                                   axis=1)

        scores = []
        for j, k_ref in enumerate(k_refs):
            slot = kb * bp + j
            start = base + slot * page
            ok = rel <= jnp.where(slot < hi, qpos - start, -1)
            if window is not None:
                ok &= rel > qpos - window - start
            s = lax.dot_general(q, page_rows(k_ref), (((1,), (1,)), ((), ())),
                                preferred_element_type=F32)
            scores.append(jnp.where(ok, s, MASKED))
        # ONE softmax step a block, as the loop takes it
        m_prev = m_s[...]
        m_new = functools.reduce(jnp.maximum, [
            jnp.max(s, axis=1, keepdims=True) for s in scores], m_prev)
        scale = jnp.exp(m_prev - m_new)
        l, acc = l_s[...] * scale, acc_s[...] * scale
        for s, v_ref in zip(scores, v_refs):
            # a masked score underflows to 0 against the block's maximum,
            # which is real: every block of a row's steps holds a key in
            # its sight
            p = jnp.exp(s - m_new)
            l += jnp.sum(p, axis=1, keepdims=True)
            acc += jnp.dot(p.astype(BF16), page_rows(v_ref),
                           preferred_element_type=F32)
        m_s[...], l_s[...], acc_s[...] = m_new, l, acc

    @pl.when((t == total - 1) | (
        (t < total) & (steps[jnp.minimum(t + 1, n_steps - 1)] != r)))
    def _():
        o_ref[...] = jnp.where(
            m_s[...] > 0.5 * MASKED,
            acc_s[...] / jnp.maximum(l_s[...], 1e-30), 0.0)


def rows_attention(q: jax.Array, qpos: jax.Array, last_pos: jax.Array,
                   pool_k: jax.Array, pool_v: jax.Array, table: jax.Array,
                   base: jax.Array, *, window: int | None, page: int,
                   block_pages: int, interpret: bool = False) -> jax.Array:
    """``q`` (S, nkv, g, hd) bfloat16, scaled, the one query of sequence
    ``s`` at position ``qpos[s]``; ``last_pos`` (S,) the last position
    that holds a key (-1: absent, reads zeros); the pools ``(pages, page,
    nkv, hd)`` or, a head in 128-lane halves, ``(pages, page, 2 nkv,
    128)``; ``table`` (S, W) physical pages from position ``base`` (S,)
    on. Returns (S, nkv, g, hd) float32."""
    rows, nkv, g, hd = q.shape
    bp, width = block_pages, table.shape[1]
    lanes = pool_k.shape[-1]
    steps = _rows_plan(qpos, last_pos, base, width=width, page=page, bp=bp,
                       window=window)
    meta = jnp.concatenate([qpos, last_pos, base]).astype(jnp.int32)
    # as many grid steps as the rows read blocks between them; the
    # interpreter takes no dynamic bound: there the steps past them are
    # skipped one by one
    n_steps = steps.shape[0] // 3
    by_row = pl.BlockSpec((None, nkv * g, hd),
                          lambda t, steps, *_: (steps[t], 0, 0))

    def page_spec(j):
        def index(t, steps, table, meta):
            slot = jnp.minimum(steps[n_steps + t] * bp + j,
                               steps[2 * n_steps + t])
            return table[steps[t] * width + slot], 0
        return pl.BlockSpec((page * nkv * hd // lanes, lanes), index)

    out = pl.pallas_call(
        functools.partial(_paged_rows_kernel, n_steps=n_steps, rows=rows,
                          nkv=nkv, g=g, page=page, bp=bp, window=window,
                          split=hd // lanes),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_steps if interpret else steps[-1],),
            in_specs=[by_row] + [page_spec(j) for _ in "kv"
                                 for j in range(bp)],
            out_specs=by_row,
            scratch_shapes=[pltpu.VMEM((nkv * g, 1), F32),
                            pltpu.VMEM((nkv * g, 1), F32),
                            pltpu.VMEM((nkv * g, hd), F32)]),
        out_shape=jax.ShapeDtypeStruct((rows, nkv * g, hd), F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="lm_rows_attention",
    )(steps, table.reshape(-1).astype(jnp.int32), meta,
      q.reshape(rows, nkv * g, hd),
      *([pool_k.reshape(-1, lanes)] * bp + [pool_v.reshape(-1, lanes)] * bp))
    return out.reshape(rows, nkv, g, hd)


# --------------------------------------------------------------------------
# latent attention: a chunk, expanded a head at a time
# --------------------------------------------------------------------------

def latent_supported(nq: int, nope: int, rope: int, rank: int, vd: int,
                     page: int) -> bool:
    """Shapes Mosaic tiles: a head's parts whole 128-lane blocks, the
    latent's parts whole sublane tiles (the rope part half a lane block
    of q), a page whole lane blocks (positions lie in the lanes), the
    chunk whole query sub-tiles."""
    tq = min(nq, LATENT_Q_TILE)
    return nope % 128 == 0 and rank % 128 == 0 and vd % 128 == 0 \
        and rope % 64 == 0 and page % 128 == 0 and nq % tq == 0 \
        and tq % 16 == 0


def _latent_kernel(table, meta, qn_ref, qr_ref, wk_ref, wv_ref, *refs,
                   tq: int, page: int, bp: int, rank: int):
    del table
    page_refs = refs[:bp]
    o_ref, k_s, v_s, m_s, l_s, acc_s = refs[bp:]
    kb = pl.program_id(1)
    nq, keys = qn_ref.shape[0], bp * page
    p0, n_pages = meta[0], meta[1]

    @pl.when(kb == 0)
    def _():
        m_s[...] = jnp.full(m_s.shape, MASKED, F32)
        l_s[...] = jnp.zeros(l_s.shape, F32)
        acc_s[...] = jnp.zeros(acc_s.shape, F32)

    # this head's keys and values of the block, once for every query.
    # A page arrives as the pool holds it, (latent, page): positions in
    # the lanes, so k and v come out transposed, (dims, keys), which is
    # what the score product wants of k anyway
    lat = jnp.concatenate([r[...] for r in page_refs], axis=1)
    c, k_rope = lat[:rank], lat[rank:]                      # (.., keys)
    k_s[...] = jnp.dot(wk_ref[...], c,
                       preferred_element_type=F32).astype(BF16)
    v_s[...] = jnp.dot(wv_ref[...], c,
                       preferred_element_type=F32).astype(BF16)
    k_lo = kb * keys

    def tile(i, edge: bool):
        at = pl.ds(pl.multiple_of(i * tq, tq), tq)
        s = jnp.dot(qn_ref[at, :], k_s[...], preferred_element_type=F32) \
            + jnp.dot(qr_ref[at, :], k_rope,
                      preferred_element_type=F32)           # (tq, keys)
        if edge:
            qpos = p0 + i * tq + lax.broadcasted_iota(
                jnp.int32, (tq, keys), 0)
            kpos = k_lo + lax.broadcasted_iota(jnp.int32, (tq, keys), 1)
            s = jnp.where((kpos <= qpos) & (kpos < n_pages * page), s,
                          MASKED)
        m_prev = m_s[at]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        scale = jnp.exp(m_prev - m_new)
        l_s[at] = l_s[at] * scale + jnp.sum(p, axis=1, keepdims=True)
        acc_s[at] = acc_s[at] * scale + lax.dot_general(
            p.astype(BF16), v_s[...], (((1,), (1,)), ((), ())),
            preferred_element_type=F32)
        m_s[at] = m_new

    def sub_tile(i, carry):
        q_lo = p0 + i * tq
        seen = k_lo <= q_lo + tq - 1
        whole = (k_lo + keys - 1 <= q_lo) & ((kb + 1) * bp <= n_pages)
        pl.when(seen & whole)(functools.partial(tile, i, False))
        pl.when(seen & jnp.logical_not(whole))(
            functools.partial(tile, i, True))
        return carry

    lax.fori_loop(0, nq // tq, sub_tile, None)

    @pl.when(kb == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = jnp.where(
            m_s[...] > 0.5 * MASKED,
            acc_s[...] / jnp.maximum(l_s[...], 1e-30), 0.0)


def latent_chunk_attention(q: jax.Array, p0: jax.Array, n_pages: jax.Array,
                           pool: jax.Array, table: jax.Array,
                           w_kvb: jax.Array, *, nope: int, page: int,
                           block_pages: int, q_tile: int = LATENT_Q_TILE,
                           interpret: bool = False) -> jax.Array:
    """``q`` (Q, heads, nope + rope) bfloat16, scaled, rope part rotated,
    at positions ``p0 + arange(Q)``; ``pool`` (pages, rank + rope, page)
    bfloat16; ``table`` (W,) physical pages from position 0 on, the
    first ``n_pages`` () of them live; ``w_kvb`` (rank, heads, nope + v)
    bfloat16. Returns (Q, heads, v) float32."""
    nq, nh, _hd = q.shape
    rank, _nh, wide = w_kvb.shape
    vd, latent = wide - nope, pool.shape[1]
    tq, bp, width = min(nq, q_tile), block_pages, table.shape[0]
    keys = bp * page
    meta = jnp.stack([p0, n_pages]).astype(jnp.int32)
    n_blocks = -(-width // bp) if interpret else \
        jnp.maximum((n_pages + bp - 1) // bp, 1)

    def page_spec(j):
        def index(h, kb, table, meta):
            last = jnp.minimum(jnp.maximum(meta[1], 1), width) - 1
            # past the last live page: stay on it (no fetch, masked)
            return table[jnp.minimum(kb * bp + j, last)], 0
        return pl.BlockSpec((latent, page), index)

    def by_head(*tail):
        return pl.BlockSpec((None,) + tail, lambda h, kb, *_: (h, 0, 0))

    by_h = q.transpose(1, 0, 2)                     # (heads, Q, nope + rope)
    w = w_kvb.transpose(1, 2, 0)                    # (heads, nope + v, rank)
    by_page = pool.reshape(-1, page)    # (pages x latent, page): a bitcast
    out = pl.pallas_call(
        functools.partial(_latent_kernel, tq=tq, page=page, bp=bp, rank=rank),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(nh, n_blocks),
            in_specs=[by_head(nq, nope), by_head(nq, latent - rank),
                      by_head(nope, rank), by_head(vd, rank)]
            + [page_spec(j) for j in range(bp)],
            out_specs=by_head(nq, vd),
            scratch_shapes=[pltpu.VMEM((nope, keys), BF16),
                            pltpu.VMEM((vd, keys), BF16),
                            pltpu.VMEM((nq, 1), F32),
                            pltpu.VMEM((nq, 1), F32),
                            pltpu.VMEM((nq, vd), F32)]),
        out_shape=jax.ShapeDtypeStruct((nh, nq, vd), F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="lm_latent_chunk_attention",
    )(table.astype(jnp.int32), meta, by_h[..., :nope], by_h[..., nope:],
      w[:, :nope], w[:, nope:], *([by_page] * bp))
    return out.transpose(1, 0, 2)


# --------------------------------------------------------------------------
# latent attention: the rows, absorbed
# --------------------------------------------------------------------------

def latent_rows_supported(nh: int, latent: int, page: int) -> bool:
    """Shapes Mosaic tiles: the heads whole sublane tiles of the score
    product's rows, the latent whole sublane tiles of a page, a page
    whole lane blocks."""
    return nh % 16 == 0 and latent % 16 == 0 and page % 128 == 0


def _rows_kernel(table, pos, q_ref, *refs, page: int, bp: int, width: int):
    del table
    page_refs = refs[:bp]
    o_ref, m_s, l_s, acc_s = refs[bp:]
    r, kb = pl.program_id(0), pl.program_id(1)
    keys = bp * page
    last = pos[r]                           # -1: the row is absent
    n_pages = jnp.where(last >= 0, last // page + 1, 0)

    @pl.when(kb == 0)
    def _():
        m_s[...] = jnp.full(m_s.shape, MASKED, F32)
        l_s[...] = jnp.zeros(l_s.shape, F32)
        acc_s[...] = jnp.zeros(acc_s.shape, F32)

    @pl.when(kb * bp < n_pages)
    def _():
        lat = jnp.concatenate([p[...] for p in page_refs], axis=1)
        s = jnp.dot(q_ref[...], lat, preferred_element_type=F32)
        kpos = kb * keys + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos <= last, s, MASKED)  # (heads, keys)
        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        scale = jnp.exp(m_prev - m_new)
        l_s[...] = l_s[...] * scale + jnp.sum(p, axis=1, keepdims=True)
        acc_s[...] = acc_s[...] * scale + lax.dot_general(
            p.astype(BF16), lat, (((1,), (1,)), ((), ())),
            preferred_element_type=F32)
        m_s[...] = m_new

    @pl.when(kb == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = jnp.where(
            m_s[...] > 0.5 * MASKED,
            acc_s[...] / jnp.maximum(l_s[...], 1e-30), 0.0)


def latent_rows_attention(q: jax.Array, last_pos: jax.Array, pool: jax.Array,
                          table: jax.Array, *, page: int, block_pages: int,
                          interpret: bool = False) -> jax.Array:
    """``q`` (S, heads, latent) bfloat16, scaled, each head's query in
    the latent's space (``[q_nope Wuk^T | q_rope]``), the one query of
    sequence ``s`` at position ``last_pos[s]`` (-1: absent, reads
    zeros); ``pool`` (pages, latent, page) bfloat16; ``table`` (S, W)
    physical pages from position 0 on. Returns (S, heads, latent)
    float32: ``softmax(q . latent) latent`` over the positions up to
    ``last_pos``, of which the caller keeps the value's lanes."""
    rows, nh, latent = q.shape
    bp, width = block_pages, table.shape[1]
    n_pages = jnp.where(last_pos >= 0, last_pos // page + 1, 0)
    n_blocks = -(-width // bp) if interpret else \
        jnp.maximum((jnp.max(n_pages) + bp - 1) // bp, 1)

    def page_spec(j):
        def index(r, kb, table, pos):
            live = jnp.where(pos[r] >= 0, pos[r] // page + 1, 0)
            last = jnp.minimum(jnp.maximum(live, 1), width) - 1
            # past the row's last page: stay on it (no fetch, no work)
            return table[r * width + jnp.minimum(kb * bp + j, last)], 0
        return pl.BlockSpec((latent, page), index)

    by_row = pl.BlockSpec((None, nh, latent), lambda r, kb, *_: (r, 0, 0))
    return pl.pallas_call(
        functools.partial(_rows_kernel, page=page, bp=bp, width=width),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(rows, n_blocks),
            in_specs=[by_row] + [page_spec(j) for j in range(bp)],
            out_specs=by_row,
            scratch_shapes=[pltpu.VMEM((nh, 1), F32),
                            pltpu.VMEM((nh, 1), F32),
                            pltpu.VMEM((nh, latent), F32)]),
        out_shape=jax.ShapeDtypeStruct((rows, nh, latent), F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="lm_latent_rows_attention",
    )(table.reshape(-1).astype(jnp.int32), last_pos.astype(jnp.int32), q,
      *([pool.reshape(-1, page)] * bp))


# --------------------------------------------------------------------------
# the choice of keys: a chunk's top scores, searched in VMEM
# --------------------------------------------------------------------------

SELECT_Q_TILE = 128     # queries a tile of the choice, searched together
LANES = 128
SIGN = -(1 << 31)       # int32 with the sign bit alone


def _ordered(bits: jax.Array) -> jax.Array:
    """float32 bits as int32 whose signed order is the floats' order."""
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


NO_KEY = -(1 << 31) + 0x7FFFFF  # ``_ordered`` of -inf's bits (0xFF800000)


def select_supported(nq: int, width: int, block: int) -> bool:
    """Shapes Mosaic tiles, in the VMEM a tile may hold: the chunk whole
    query tiles of whole int8 mask tiles, a block whole lane blocks, the
    score row whole blocks; a tile's row of keys as int32 and its mask,
    twice, under three quarters of ``VMEM_LIMIT``."""
    tq = min(nq, SELECT_Q_TILE)
    held = tq * width * (4 + 2) + 2 * tq * block * 4
    return nq % tq == 0 and tq % 32 == 0 and block % LANES == 0 \
        and width % block == 0 and held <= VMEM_LIMIT * 3 // 4


def _select_blocks(meta, qi, *, tq: int, block: int):
    """The blocks of the score row that hold a key some query of tile
    ``qi`` may choose (causal, the first ``n_keys`` columns)."""
    p0, n_keys = meta[0], meta[1]
    end = jnp.minimum(n_keys, p0 + (qi + 1) * tq)
    return jnp.maximum(end + block - 1, 0) // block


def _select_kernel(meta, s_ref, o_ref, key_s, *, tq: int, block: int,
                   top: int, width: int):
    qi, kb = pl.program_id(0), pl.program_id(1)
    nb = _select_blocks(meta, qi, tq=tq, block=block)

    @pl.when(kb < nb)
    def _():
        key_s[kb] = _ordered(lax.bitcast_convert_type(s_ref[...], jnp.int32))

    @pl.when(kb == pl.num_programs(1) - 1)
    def _():
        _choose(key_s, o_ref, nb, tq=tq, block=block, top=top, width=width)

        def clear(c, carry):
            # nothing past the tile's last block is a key it may choose
            o_ref[:, pl.ds(pl.multiple_of(c * block, block), block)] = \
                jnp.zeros((tq, block), jnp.int8)
            return carry

        lax.fori_loop(nb, width // block, clear, None)


def _choose(key_s, o_ref, nb, *, tq: int, block: int, top: int,
            width: int):
    """Find each of the tile's queries' ``top``-th largest key over its
    ``nb`` live blocks, one bit a pass, then, where ties at it straddle
    the cut, the position that splits them; write the tile's mask."""
    shape = (tq, LANES)
    lane = lax.broadcasted_iota(jnp.int32, shape, 1)

    def count(pred):
        """Per query ``(tq, 1)``, the live columns where ``pred(keys,
        first column)`` holds: one read of the keys, lane-wise partial
        sums reduced once."""
        def body(c, acc):
            for j in range(block // LANES):
                keys = key_s[c, :, pl.ds(j * LANES, LANES)]
                acc = acc + jnp.where(pred(keys, c * block + j * LANES), 1, 0)
            return acc
        acc = lax.fori_loop(0, nb, body, jnp.zeros(shape, jnp.int32))
        return jnp.sum(acc, axis=1, keepdims=True)

    def settle(p, carry):
        """Bit ``31 - p`` of the k-th key (its bits as unsigned; a
        candidate is compared in the signed order with its sign bit
        flipped): set where ``top`` keys still reach it; and how many
        reach the k-th as it stands."""
        kth, reach = carry
        cand = kth | (1 << (31 - p))
        cand_b = jnp.broadcast_to(cand ^ SIGN, shape)
        n = count(lambda k, _: k >= cand_b)
        return jnp.where(n >= top, cand, kth), jnp.where(n >= top, n, reach)

    zero = jnp.zeros((tq, 1), jnp.int32)
    kth, reach = lax.fori_loop(0, 32, settle, (zero, zero))
    kth = kth ^ SIGN
    # fewer than ``top`` keys: the k-th is no key, and every key is chosen
    thr = jnp.maximum(kth, NO_KEY + 1)
    split = (kth > NO_KEY) & (reach > top)
    thr_b = jnp.broadcast_to(thr, shape)

    def ties():
        """The largest position ``P`` at which the keys above the k-th and
        the ties before ``P`` are still fewer than ``top``: the cut's
        tie is at ``P``, the ties after it are left out."""
        def bisect(p, cut):
            cand = cut | (1 << (bits - 1 - p))
            cand_b = jnp.broadcast_to(cand, shape)
            n = count(lambda k, col: (k > thr_b) | (
                (k == thr_b) & (lane < cand_b - col)))
            return jnp.where(n < top, cand, cut)
        bits = max(width - 1, 1).bit_length()
        return jnp.where(split, lax.fori_loop(0, bits, bisect, zero),
                         jnp.int32(width))

    cut = lax.cond(jnp.max(split.astype(jnp.int32)) > 0, ties,
                   lambda: jnp.full((tq, 1), width, jnp.int32))
    cut_b = jnp.broadcast_to(cut, shape)

    def write(c, carry):
        for j in range(block // LANES):
            col = c * block + j * LANES
            keys = key_s[c, :, pl.ds(j * LANES, LANES)]
            chosen = (keys > thr_b) | ((keys == thr_b) & (lane <= cut_b - col))
            o_ref[:, pl.ds(pl.multiple_of(col, LANES), LANES)] = \
                jnp.where(chosen, 1, 0).astype(jnp.int8)
        return carry

    lax.fori_loop(0, nb, write, None)


def select_keys_kernel(scores: jax.Array, top: int, p0: jax.Array,
                       n_keys: jax.Array, *, block: int,
                       q_tile: int = SELECT_Q_TILE,
                       interpret: bool = False) -> jax.Array:
    """``model.py::select_keys`` for ONE chunk: ``scores`` (Q, W) float32,
    query ``i`` at position ``p0 + i``, ``-inf`` at what is no key (past
    a query's position and past ``n_keys``: ``index_scores``). Returns
    (Q, W) int8, 1 where chosen: every key of a query that has at most
    ``top``, else its ``top`` largest, ties to the lower position; the
    same set as ``select_keys``, bit for bit."""
    nq, width = scores.shape
    tq = min(nq, q_tile)
    meta = jnp.stack([p0, n_keys]).astype(jnp.int32)
    # as many key blocks as the chunk's last query reads; the interpreter
    # takes no dynamic bound: there the steps past them do nothing
    n_blocks = width // block if interpret else \
        jnp.maximum((n_keys + block - 1) // block, 1)

    def score_block(qi, kb, meta):
        """Tile ``qi``'s block at step ``kb``: past its last live one it
        stays on that one (no fetch)."""
        last = _select_blocks(meta, qi, tq=tq, block=block) - 1
        return qi, jnp.clip(kb, 0, jnp.maximum(last, 0))

    return pl.pallas_call(
        functools.partial(_select_kernel, tq=tq, block=block, top=top,
                          width=width),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(nq // tq, n_blocks),
            in_specs=[pl.BlockSpec((tq, block), score_block)],
            out_specs=pl.BlockSpec((tq, width), lambda qi, kb, meta: (qi, 0)),
            scratch_shapes=[pltpu.VMEM((width // block, tq, block),
                                       jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((nq, width), jnp.int8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="lm_select_keys",
    )(meta, scores)
