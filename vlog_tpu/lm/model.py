"""The transcript model: configuration, layer mathematics, step program.

Two published families share the step program's frame, the paged cache
and the expert layer; ``LmConfig.model_type`` chooses the block.

**``afmoe``.** Every layer is
``h = h + N2(Attn(N1(h)))`` then ``h = h + N4(Mlp(N3(h)))`` (RMSNorm
before and after each block). Attention has 8 query heads a K/V head,
an RMSNorm over each head's dims of ``q`` and ``k``, rotary embedding on
``sliding_attention`` layers only (``full_attention`` layers carry no
positions), a band of ``sliding_window`` keys on the window layers, and
an output gate ``sigmoid(x Wg)`` on the concatenated heads. The first
``num_dense_layers`` MLPs are SwiGLU; the rest are the expert layer of
``moe.py``. The embedding is scaled by ``sqrt(hidden)`` (``mup_enabled``)
and the head is untied.

**``KeyeVL2``** (the language model; the vision tower is not built).
Every layer is ``h = h + Attn(N1(h)) Wo`` then ``h = h + Moe(N2(h))``
(two RMSNorms, no gate, no dense layer, no shared expert, softmax
routing), rotary embedding on every layer, and a learned sparse
attention: an indexer of ``index_heads`` heads of ``index_head_dim``
dims scores every causal key (``I[t, s] = sum_j w[t, j] relu(qI[t, j] .
kI[s])``, float32), each query keeps its ``index_topk`` best keys (all
of them while it has no more; ties to the lower position) and attends
over those alone, one choice for all heads. The indexer's keys live in
a second pool beside K and V on the full class's page numbers.
:func:`index_scores` computes the scores over a sequence's pages,
:func:`select_keys` finds each query's k-th best score digit by digit
over the float32 bits (exact; the scores are read, never sorted) and
:func:`top_positions` is ``lax.top_k`` for the few decoding rows. Two
forms of the attention compute the same thing: :func:`paged_attention`
masked by the choice (a prefill chunk: 2,048 queries share the pages
they read) and :func:`gathered_attention` over the chosen keys alone (a
decoding row: ``index_topk`` keys of K and V in place of its whole
context).

Precision as stated: weights, K/V and indexer keys bfloat16, products
accumulate in float32, the residual stream, norms, router, index
scores, softmax and logits float32.

**The step program** (:func:`build_step`) serves one engine step: at
most one prefill chunk of ONE request (``chunk`` tokens, a static
bucket) beside every decoding row (``rows``, one token each). All of a
step's tokens pass the projections, the dense MLPs and the experts
together; attention runs per sequence over the paged cache: the chunk's
and the rows' new K/V are written into their pages first, then each
query block reads its sequence's pages ``kv_block_pages`` at a time
under an online softmax (never more than queries x block scores; for a
chunk on a TPU inside one kernel, ``attention_kernel.py``, where they
never leave the chip), from the table's first page to the page of its
last query. A table is a
list of physical pages from logical page ``base // page`` on: for a
full layer ``base`` is 0, for a window layer the host hands in only the
pages the band touches (``cache.py``), so a window layer visits no page
behind its window. Physical page 0 is never handed out: unallocated
table slots and padded rows point there, reads of it are masked.

The next token (greedy) goes back in on the device: ``last_tok`` holds
each row's newest token, the step overwrites it, and a request's last
prefill chunk deposits its first token at the row it will decode in.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from vlog_tpu.lm import attention_kernel, moe

F32 = jnp.float32
BF16 = jnp.bfloat16
SLIDING = "sliding_attention"
FULL = "full_attention"
MASKED = -1e30


@dataclass(frozen=True)
class LmConfig:
    """The model's shape, from a published ``config.json`` (HF keys).
    What only one family has is zero, empty or ``False`` for the other."""

    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    layer_types: tuple[str, ...]        # of the layers held here
    num_dense_layers: int
    intermediate_size: int
    moe_intermediate_size: int
    num_experts: int
    num_experts_per_tok: int
    num_shared_experts: int
    route_norm: bool
    route_scale: float
    sliding_window: int
    vocab_size: int
    rms_norm_eps: float
    rope_theta: float
    mup_enabled: bool
    model_type: str = "afmoe"
    score_func: str = "sigmoid"         # or softmax
    index_heads: int = 0                # the sparse attention's indexer
    index_head_dim: int = 0
    index_topk: int = 0                 # keys a query attends (0: all)

    @classmethod
    def from_hf(cls, d: dict) -> "LmConfig":
        families = {"afmoe": cls._from_afmoe, "KeyeVL2": cls._from_keye}
        family = d.get("model_type", "afmoe")
        if family not in families:
            raise ValueError(f"model_type {family!r} is not built (built: "
                             f"{', '.join(families)})")
        return families[family](d)

    @classmethod
    def _from_afmoe(cls, d: dict) -> "LmConfig":
        n = int(d["num_hidden_layers"])
        kinds = tuple(d["layer_types"][:n])
        if len(kinds) != n:
            raise ValueError("layer_types is shorter than num_hidden_layers")
        if d.get("score_func", "sigmoid") != "sigmoid" \
                or int(d.get("n_group", 1)) != 1 \
                or int(d.get("topk_group", 1)) != 1:
            raise ValueError("afmoe: only sigmoid routing in one group is "
                             "built")
        return cls(
            hidden_size=int(d["hidden_size"]),
            num_attention_heads=int(d["num_attention_heads"]),
            num_key_value_heads=int(d["num_key_value_heads"]),
            head_dim=int(d["head_dim"]), layer_types=kinds,
            num_dense_layers=int(d["num_dense_layers"]),
            intermediate_size=int(d["intermediate_size"]),
            moe_intermediate_size=int(d["moe_intermediate_size"]),
            num_experts=int(d["num_experts"]),
            num_experts_per_tok=int(d["num_experts_per_tok"]),
            num_shared_experts=int(d["num_shared_experts"]),
            route_norm=bool(d["route_norm"]),
            route_scale=float(d["route_scale"]),
            sliding_window=int(d["sliding_window"]),
            vocab_size=int(d["vocab_size"]),
            rms_norm_eps=float(d["rms_norm_eps"]),
            rope_theta=float(d["rope_theta"]),
            mup_enabled=bool(d.get("mup_enabled", False)))

    @classmethod
    def _from_keye(cls, d: dict) -> "LmConfig":
        """The language model of ``KeyeVL2``: every layer sparse
        attention and experts. What the family can say and this program
        does not run is refused by name."""
        sa = d.get("sa_config") or {}
        refused = {
            "mlp_only_layers": bool(d.get("mlp_only_layers")),
            "decoder_sparse_step != 1":
                int(d.get("decoder_sparse_step", 1)) != 1,
            "use_sliding_window": bool(d.get("use_sliding_window")),
            "attention_bias": bool(d.get("attention_bias")),
            "tie_word_embeddings": bool(d.get("tie_word_embeddings")),
            "sa_config.indexer_num_kv_heads != 1":
                int(sa.get("indexer_num_kv_heads", 1)) != 1,
            "no sa_config.topk": int(sa.get("topk", 0)) < 1}
        bad = [name for name, hit in refused.items() if hit]
        if bad:
            raise ValueError(f"KeyeVL2: not built: {', '.join(bad)}")
        return cls(
            hidden_size=int(d["hidden_size"]),
            num_attention_heads=int(d["num_attention_heads"]),
            num_key_value_heads=int(d["num_key_value_heads"]),
            head_dim=int(d["head_dim"]),
            layer_types=(FULL,) * int(d["num_hidden_layers"]),
            num_dense_layers=0,
            intermediate_size=int(d["intermediate_size"]),
            moe_intermediate_size=int(d["moe_intermediate_size"]),
            num_experts=int(d["num_experts"]),
            num_experts_per_tok=int(d["num_experts_per_tok"]),
            num_shared_experts=0,
            route_norm=bool(d.get("norm_topk_prob", True)),
            route_scale=1.0, sliding_window=0,
            vocab_size=int(d["vocab_size"]),
            rms_norm_eps=float(d["rms_norm_eps"]),
            rope_theta=float(d["rope_theta"]), mup_enabled=False,
            model_type="KeyeVL2", score_func="softmax",
            index_heads=int(sa["indexer_num_heads"]),
            index_head_dim=int(sa["indexer_head_dim"]),
            index_topk=int(sa["topk"]))

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def window_layers(self) -> int:
        return sum(k == SLIDING for k in self.layer_types)

    @property
    def full_layers(self) -> int:
        return self.num_layers - self.window_layers

    def position_bytes(self) -> tuple[int, int]:
        """Cache bytes one position costs over the layers held here, by
        class ``(window, full)``: K and V, and the indexer's key."""
        kv = 2 * self.num_key_value_heads * self.head_dim * 2
        index = self.index_head_dim * 2 if self.index_topk else 0
        return (self.window_layers * kv, self.full_layers * (kv + index))


@dataclass(frozen=True)
class Geometry:
    """How the engine lays a deployment onto the device."""

    rows: int = 32              # decoding rows a step
    chunk: int = 2048           # most prefill tokens a step
    page: int = 256             # positions a page
    context_cap: int = 40_960   # longest prompt + output served
    window_pages: int = 0       # pool size of the window class (page 0 incl.)
    full_pages: int = 0         # pool size of the full class (page 0 incl.)
    kv_block_pages: int = 4     # key pages an attention block reads

    def ring(self, window: int) -> int:
        """Pages a window table holds at most: the window plus one chunk
        (0 for a model without window layers: no table at all)."""
        return (window + self.chunk) // self.page if window else 0

    @property
    def max_pages(self) -> int:
        return -(-self.context_cap // self.page)

    def chunk_buckets(self) -> tuple[int, ...]:
        """0 (decode only), then page, 2 page, ... up to chunk."""
        out, c = [0], self.page
        while c < self.chunk:
            out.append(c)
            c *= 2
        return tuple(out + [self.chunk])

    @property
    def key_width(self) -> int:
        """Positions a sequence's score row holds: the context cap,
        rounded up to whole attention blocks."""
        block = self.kv_block_pages * self.page
        return -(-self.context_cap // block) * block

    def check(self, cfg: LmConfig) -> None:
        if self.chunk % self.page or cfg.sliding_window % self.page:
            raise ValueError("chunk and window must be whole pages")
        if self.context_cap % self.page:
            raise ValueError("context_cap must be whole pages")
        if cfg.window_layers and self.window_pages < 2 \
                or cfg.full_layers and self.full_pages < 2:
            raise ValueError("a layer class needs a pool of page 0 and at "
                             "least one more")
        if not cfg.window_layers and self.window_pages:
            raise ValueError("no window layer: a window pool would hold "
                             "nothing (window_pages must be 0)")
        if cfg.index_topk > self.key_width:
            raise ValueError("index_topk exceeds the context cap")


# --------------------------------------------------------------------------
# layer mathematics
# --------------------------------------------------------------------------

def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    x = x.astype(F32)
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


def rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """Rotate-half rotary embedding over the whole head: ``x`` (T, heads,
    hd) float32, ``pos`` (T,) absolute positions."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos.astype(F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], axis=-1) \
        * jnp.sin(ang)


def mm(x: jax.Array, w: jax.Array) -> jax.Array:
    return jnp.dot(x.astype(BF16), w, preferred_element_type=F32)


def layer_norm(x: jax.Array, w: jax.Array, b: jax.Array, eps: float
               ) -> jax.Array:
    x = x.astype(F32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * w.astype(F32) + b.astype(F32)


def attention_form(seqs: int, nq: int, nkv: int, hd: int, page: int) -> str:
    """``"kernel"`` or ``"loop"``: the form :func:`paged_attention` takes
    for ``seqs`` sequences of ``nq`` queries each."""
    chunk = seqs == 1 and nq > 1 and attention_kernel.supported(
        nq, nkv, hd, page)
    return "kernel" if chunk and jax.default_backend() == "tpu" else "loop"


def paged_attention(q: jax.Array, qpos: jax.Array, last_pos: jax.Array,
                    pool_k: jax.Array, pool_v: jax.Array, table: jax.Array,
                    base: jax.Array, *, window: int | None, page: int,
                    block_pages: int, chosen: jax.Array | None = None
                    ) -> tuple[jax.Array, jax.Array]:
    """Online-softmax attention of a batch of sequences over their pages.

    ``q`` (S, Q, nkv, g, hd) bfloat16, already scaled; ``qpos`` (S, Q)
    absolute positions of the queries; ``last_pos`` (S,) the last
    position that holds a key (-1: the sequence is absent); ``table``
    (S, W) physical pages from position ``base`` (S,) on; ``chosen``
    (S, Q, keys) bool, where given, the keys each query attends (by
    position from ``base`` on; whole blocks wide). Returns
    ``(out (S, Q, nkv, g, hd) float32, pages visited (S,))``.

    Two forms of the one algorithm, chosen by :func:`attention_form`
    from the call's shapes and the backend: rows of one query each run
    the loop below; a prefill chunk (one sequence of ``Q`` consecutive
    positions from ``qpos[0, 0]`` on) runs on a TPU as one kernel that
    keeps a block's scores on the chip (``attention_kernel.py``).
    """
    s, nq, nkv, g, hd = q.shape
    width = table.shape[1]
    keys = block_pages * page
    n_pages = jnp.where(last_pos >= 0, (last_pos - base) // page + 1, 0)
    if attention_form(s, nq, nkv, hd, page) == "kernel":
        out = attention_kernel.chunk_attention(
            q[0], qpos[0, 0], n_pages[0], pool_k, pool_v, table[0], base[0],
            window=window, page=page, block_pages=block_pages,
            chosen=None if chosen is None else chosen[0])
        return out[None], n_pages
    n_blocks = (jnp.max(n_pages) + block_pages - 1) // block_pages
    lane = jnp.arange(keys, dtype=jnp.int32)

    def body(i, carry):
        m, l, acc = carry
        slots = i * block_pages + jnp.arange(block_pages, dtype=jnp.int32)
        live = slots[None, :] < n_pages[:, None]              # (S, bp)
        phys = jnp.where(live, jnp.take(table, jnp.minimum(slots, width - 1),
                                        axis=1), 0)
        k = pool_k[phys].reshape(s, keys, nkv, hd)
        v = pool_v[phys].reshape(s, keys, nkv, hd)
        kpos = base[:, None] + i * keys + lane[None, :]       # (S, K)
        ok = kpos[:, None, :] <= qpos[:, :, None]             # (S, Q, K)
        if window is not None:
            ok &= kpos[:, None, :] > qpos[:, :, None] - window
        ok &= jnp.repeat(live, page, axis=1)[:, None, :]
        if chosen is not None:
            ok &= lax.dynamic_slice_in_dim(chosen, i * keys, keys, axis=2)
        sc = jnp.einsum("sqngd,sknd->sngqk", q, k,
                        preferred_element_type=F32)
        sc = jnp.where(ok[:, None, None, :, :], sc, MASKED)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
        p = jnp.where(ok[:, None, None, :, :],
                      jnp.exp(sc - m_new[..., None]), 0.0)
        scale = jnp.exp(m - m_new)
        l = l * scale + jnp.sum(p, axis=-1)
        acc = acc * scale[..., None] + jnp.einsum(
            "sngqk,sknd->sngqd", p.astype(BF16), v,
            preferred_element_type=F32)
        return m_new, l, acc

    m0 = jnp.full((s, nkv, g, nq), MASKED, F32)
    l0 = jnp.zeros((s, nkv, g, nq), F32)
    a0 = jnp.zeros((s, nkv, g, nq, hd), F32)
    _, l, acc = lax.fori_loop(0, n_blocks, body, (m0, l0, a0))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4), n_pages


# --------------------------------------------------------------------------
# learned sparse attention: index scores, the choice, attention over it
# --------------------------------------------------------------------------

def index_scores(qi: jax.Array, wi: jax.Array, qpos: jax.Array,
                 last_pos: jax.Array, pool_ki: jax.Array, table: jax.Array,
                 *, page: int, block_pages: int, width: int) -> jax.Array:
    """The indexer's score of every causal key, for a batch of
    sequences over their pages.

    ``qi`` (S, Q, J, D) bfloat16 (rotated), ``wi`` (S, Q, J) float32
    (scaled), ``qpos`` (S, Q), ``last_pos`` (S,) as for
    :func:`paged_attention`, ``pool_ki`` (pages, page, D) bfloat16,
    ``table`` (S, W) from position 0 on. Returns ``(S, Q, width)``
    float32: ``sum_j wi[j] * relu(qi[j] . ki[s])`` at key positions
    ``s <= qpos`` that hold a key, ``-inf`` elsewhere; never ``-0.0``.
    """
    s, nq, _heads, dim = qi.shape
    keys = block_pages * page
    n_pages = jnp.where(last_pos >= 0, last_pos // page + 1, 0)
    n_blocks = (jnp.max(n_pages) + block_pages - 1) // block_pages
    lane = jnp.arange(keys, dtype=jnp.int32)
    wt = wi.transpose(0, 2, 1)[..., None]                     # (S, J, Q, 1)

    def body(i, out):
        slots = i * block_pages + jnp.arange(block_pages, dtype=jnp.int32)
        live = slots[None, :] < n_pages[:, None]
        phys = jnp.where(live, jnp.take(
            table, jnp.minimum(slots, table.shape[1] - 1), axis=1), 0)
        ki = pool_ki[phys].reshape(s, keys, dim)
        dots = jnp.einsum("sqjd,skd->sjqk", qi, ki,
                          preferred_element_type=F32)
        score = jnp.sum(jnp.maximum(dots, 0.0) * wt, axis=1)  # (S, Q, K)
        score = jnp.where(score == 0.0, 0.0, score)           # no -0.0
        kpos = i * keys + lane
        ok = (kpos[None, None, :] <= qpos[:, :, None]) \
            & jnp.repeat(live, page, axis=1)[:, None, :]
        return lax.dynamic_update_slice_in_dim(
            out, jnp.where(ok, score, -jnp.inf), i * keys, axis=2)

    return lax.fori_loop(0, n_blocks, body,
                         jnp.full((s, nq, width), -jnp.inf, F32))


def _ordered_bits(x: jax.Array) -> jax.Array:
    """float32 -> uint32 whose unsigned order is the floats' order."""
    bits = lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


RADIX_BITS = 2      # bits of the k-th score that one pass settles


def select_keys(scores: jax.Array, top: int, n_keys: jax.Array, *,
                block: int) -> jax.Array:
    """Each query's ``top`` best-scored keys as a mask, exactly, with no
    sort: ``scores`` (S, Q, W) float32 with ``-inf`` at what is no key
    (:func:`index_scores`), ``n_keys`` () how many leading columns hold
    any key (the rest are not read). Returns (S, Q, W) bool: every key
    of a query that has at most ``top``; else the ``top`` of largest
    score, ties to the lower position (``lax.top_k``'s set).

    The k-th largest score of a row is found digit by digit over the 32
    bits of its ordered image, ``RADIX_BITS`` a pass: a pass counts, for
    each value of the next digit, the scores that reach the candidate
    (one read of the first ``n_keys`` columns, in blocks of ``block``,
    compared against every candidate) and keeps the largest digit that
    ``top`` scores still reach. A tie AT the threshold that the cut
    splits is resolved by a prefix count, in a branch taken only when
    some row has one.
    """
    s, nq, _width = scores.shape
    n_blocks = (n_keys + block - 1) // block
    bits = _ordered_bits(scores)
    floor = _ordered_bits(jnp.float32(-jnp.inf))
    digits = jnp.arange(1, 1 << RADIX_BITS, dtype=jnp.uint32)[:, None, None]

    def count(pred, lead=()):
        """Per row, the columns below ``n_blocks * block`` where
        ``pred(block of bits)`` holds."""
        def body(i, total):
            part = lax.dynamic_slice_in_dim(bits, i * block, block, axis=2)
            return total + jnp.sum(pred(part), axis=-1, dtype=jnp.int32)
        return lax.fori_loop(0, n_blocks, body,
                             jnp.zeros(lead + (s, nq), jnp.int32))

    def settle(b, prefix):
        shift = jnp.uint32(32 - RADIX_BITS) - b.astype(jnp.uint32) \
            * RADIX_BITS
        cands = prefix[None] | (digits << shift)            # (D, S, Q)
        reach = count(lambda part: part[None] >= cands[..., None],
                      lead=cands.shape[:1])
        # fewer scores reach a larger candidate: those that ``top``
        # reach are the first few, and their number is the digit
        digit = jnp.sum(reach >= top, axis=0).astype(jnp.uint32)
        return prefix | (digit << shift)

    # the largest value that at least ``top`` scores reach: the k-th best
    kth = lax.fori_loop(0, 32 // RADIX_BITS, settle,
                        jnp.zeros((s, nq), jnp.uint32))
    kth = jnp.maximum(kth, floor + 1)       # fewer than top keys: all
    above = count(lambda part: part > kth[..., None])
    at = count(lambda part: part == kth[..., None])
    room = top - above                      # of the ties, how many fit
    valid = bits > floor

    def split(_):
        tied = bits == kth[..., None]
        before = jnp.cumsum(tied, axis=-1, dtype=jnp.int32) - tied
        return valid & ((bits > kth[..., None])
                        | (tied & (before < room[..., None])))

    def whole(_):
        return valid & (bits >= kth[..., None])

    return lax.cond(jnp.any(at > room), split, whole, None)


def top_positions(scores: jax.Array, top: int) -> tuple[jax.Array, jax.Array]:
    """``lax.top_k`` for a few rows: ``scores`` (S, W) -> ``(positions
    (S, top) int32, which of them are keys (S, top) bool)``."""
    vals, idx = lax.top_k(scores, top)
    return idx.astype(jnp.int32), vals > -jnp.inf


def gathered_attention(q: jax.Array, positions: jax.Array, live: jax.Array,
                       pool_k: jax.Array, pool_v: jax.Array,
                       table: jax.Array, *, page: int) -> jax.Array:
    """Attention of one query a sequence over the keys at ``positions``
    alone: ``q`` (S, nkv, g, hd) bfloat16, scaled; ``positions`` (S, K),
    ``live`` (S, K) bool; ``table`` (S, W) from position 0 on. Returns
    (S, nkv, g, hd) float32 (zeros where nothing is live)."""
    phys = jnp.where(live, jnp.take_along_axis(
        table, jnp.minimum(positions // page, table.shape[1] - 1), axis=1), 0)
    k = pool_k[phys, positions % page]                  # (S, K, nkv, hd)
    v = pool_v[phys, positions % page]
    sc = jnp.einsum("sngd,sknd->sngk", q, k, preferred_element_type=F32)
    sc = jnp.where(live[:, None, None, :], sc, MASKED)
    p = jnp.where(live[:, None, None, :],
                  jnp.exp(sc - jnp.max(sc, axis=-1, keepdims=True)), 0.0)
    acc = jnp.einsum("sngk,sknd->sngd", p.astype(BF16), v,
                     preferred_element_type=F32)
    return acc / jnp.maximum(jnp.sum(p, axis=-1), 1e-30)[..., None]


# --------------------------------------------------------------------------
# the step program
# --------------------------------------------------------------------------

def empty_cache(cfg: LmConfig, geo: Geometry) -> dict:
    """Per layer one K and one V pool ``(pages, page, nkv, hd)`` and,
    where the model has an indexer, one pool of its keys ``(pages, page,
    index_head_dim)``; the layers of a class share page numbers
    (``cache.py``), and a layer's three pools share them too."""
    sizes = [geo.window_pages if k == SLIDING else geo.full_pages
             for k in cfg.layer_types]

    def pools(*tail):
        return [jnp.zeros((n, geo.page) + tail, BF16) for n in sizes]

    out = {"k": pools(cfg.num_key_value_heads, cfg.head_dim),
           "v": pools(cfg.num_key_value_heads, cfg.head_dim)}
    if cfg.index_topk:
        out["ki"] = pools(cfg.index_head_dim)
    return out


def unpack_ints(cfg: LmConfig, geo: Geometry, ints) -> dict:
    """A step's ``out["ints"]`` (on the host) by name. The last two are
    ``pages`` (a model with window layers) or ``keys`` (one with an
    indexer): what one layer read over what a causal-dense one would."""
    r = geo.rows + 1
    n_moe = cfg.num_layers - cfg.num_dense_layers
    return {"tokens": ints[:r],
            "expert_load": ints[r:r + 3 * n_moe].reshape(n_moe, 3),
            "keys" if cfg.index_topk else "pages": ints[r + 3 * n_moe:]}


def plan_shapes(cfg: LmConfig, geo: Geometry, chunk: int) -> dict:
    """``{name: (shape, dtype)}`` of the plan a step of this bucket
    takes (the host stacks it, ``engine.py``). A model without window
    layers has no window table."""
    ring, r = geo.ring(cfg.sliding_window), geo.rows
    out = {"row_active": ((r,), jnp.bool_), "row_pos": ((r,), jnp.int32),
           "row_ftab": ((r, geo.max_pages), jnp.int32)}
    if cfg.window_layers:
        out.update({"row_wtab": ((r, ring), jnp.int32),
                    "row_wbase": ((r,), jnp.int32)})
    if chunk:
        out.update({"chunk_ids": ((chunk,), jnp.int32),
                    # p0, n, row (-1: not the last chunk), window base
                    "chunk_meta": ((4,), jnp.int32),
                    "chunk_ftab": ((geo.max_pages,), jnp.int32)})
        if cfg.window_layers:
            out["chunk_wtab"] = ((ring,), jnp.int32)
    return out


@dataclass
class _Step:
    """What every layer of one step reads: the tokens' positions and the
    plan. Chunk tokens come first, then the rows'."""

    chunk: int
    plan: dict
    pos: jax.Array
    valid: jax.Array
    row_pos: jax.Array
    row_on: jax.Array
    row_last: jax.Array
    p0: jax.Array | None = None
    n: jax.Array | None = None
    cwbase: jax.Array | None = None
    offs: jax.Array | None = None

    @property
    def chunk_last(self) -> jax.Array:
        return jnp.where(self.n > 0, self.p0 + self.n - 1, -1)


def _write_pages(st: _Step, geo: Geometry, pools: list, new: list,
                 ctab, cbase, row_tab, row_base) -> list:
    """The chunk's and the rows' new entries into each pool of one layer
    (``new`` (T, ...) a pool: K, V, the indexer's key)."""
    page, chunk = geo.page, st.chunk
    if chunk:
        first = (st.p0 - cbase) // page
        for j in range(chunk // page):
            slot = jnp.minimum(first + j, ctab.shape[0] - 1)
            phys = jnp.where(j * page < st.n, ctab[slot], 0)
            pools = [lax.dynamic_update_slice(
                pool, x[None, j * page:(j + 1) * page],
                (phys,) + (0,) * (pool.ndim - 1))
                for pool, x in zip(pools, new)]
    slot = jnp.clip((st.row_pos - row_base) // page, 0, row_tab.shape[1] - 1)
    phys = jnp.where(st.row_on, jnp.take_along_axis(
        row_tab, slot[:, None], axis=1)[:, 0], 0)
    return [pool.at[phys, st.row_pos % page].set(x[chunk:])
            for pool, x in zip(pools, new)]


def _experts(cfg: LmConfig, lp: dict, x: jax.Array, valid: jax.Array):
    """Router and routed experts of one layer: ``(y, load (3,))``."""
    chosen, weights, _ = moe.route(
        x, lp["router"], lp.get("bias"), top_k=cfg.num_experts_per_tok,
        route_norm=cfg.route_norm, route_scale=cfg.route_scale,
        score_func=cfg.score_func)
    y, counted, held = moe.experts(x, chosen, weights, lp["e_gate"],
                                   lp["e_up"], lp["e_down"], valid)
    return y, jnp.stack([jnp.max(counted), jnp.sum(counted), held])


def _afmoe_layer(cfg: LmConfig, geo: Geometry, st: _Step, li: int, lp: dict,
                 h: jax.Array, kv: dict):
    """One ``afmoe`` layer; returns ``(h, expert load or None, (pages
    visited, pages a causal-full layer would) or None)``."""
    chunk, page, plan = st.chunk, geo.page, st.plan
    nh, nkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    g, eps = nh // nkv, cfg.rms_norm_eps
    is_window = cfg.layer_types[li] == SLIDING
    window = cfg.sliding_window if is_window else None
    x = rms_norm(h, lp["n1"], eps)
    q = rms_norm(mm(x, lp["wq"]).reshape(-1, nh, hd), lp["qn"], eps)
    k = rms_norm(mm(x, lp["wk"]).reshape(-1, nkv, hd), lp["kn"], eps)
    v = mm(x, lp["wv"]).reshape(-1, nkv, hd)
    if is_window:
        q = rope(q, st.pos, cfg.rope_theta)
        k = rope(k, st.pos, cfg.rope_theta)
    q = (q * (1.0 / math.sqrt(hd))).astype(BF16).reshape(-1, nkv, g, hd)
    k, v = k.astype(BF16), v.astype(BF16)
    if is_window:
        row_tab, row_base = plan["row_wtab"], plan["row_wbase"]
    else:
        row_tab, row_base = plan["row_ftab"], jnp.zeros_like(st.row_pos)
    ctab = cbase = None
    if chunk:
        ctab = plan["chunk_wtab"] if is_window else plan["chunk_ftab"]
        cbase = st.cwbase if is_window else jnp.int32(0)
    with jax.named_scope("lm.cache.write"):
        pk, pv = _write_pages(st, geo, [kv["k"][li], kv["v"][li]], [k, v],
                              ctab, cbase, row_tab, row_base)
    kv["k"][li], kv["v"][li] = pk, pv
    pages = None
    with jax.named_scope("lm.attn.window" if is_window else "lm.attn.full"):
        o_rows, seen = paged_attention(
            q[chunk:, None], st.row_pos[:, None], st.row_last, pk, pv,
            row_tab, row_base, window=window, page=page,
            block_pages=geo.kv_block_pages)
        o = o_rows[:, 0]
        would = jnp.where(st.row_on, st.row_pos // page + 1, 0)
        if chunk:
            o_chunk, c_seen = paged_attention(
                q[None, :chunk], (st.p0 + st.offs)[None],
                st.chunk_last[None], pk, pv, ctab[None], cbase[None],
                window=window, page=page, block_pages=geo.kv_block_pages)
            o = jnp.concatenate([o_chunk[0], o])
            seen = jnp.concatenate([seen, c_seen])
            would = jnp.concatenate([would, jnp.where(
                st.n > 0, (st.p0 + st.n - 1) // page + 1, 0)[None]])
        if is_window and li == cfg.layer_types.index(SLIDING):
            pages = jnp.stack([jnp.sum(seen), jnp.sum(would)])
    with jax.named_scope("lm.attn.gate"):
        o = o.reshape(-1, nh * hd) * jax.nn.sigmoid(mm(x, lp["wg"]))
    h = h + rms_norm(mm(o, lp["wo"]), lp["n2"], eps)

    x = rms_norm(h, lp["n3"], eps)
    load = None
    if li < cfg.num_dense_layers:
        with jax.named_scope("lm.mlp.dense"):
            y = moe.swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"])
    else:
        y, load = _experts(cfg, lp, x, st.valid)
        if cfg.num_shared_experts:
            with jax.named_scope("lm.moe.shared"):
                y = y + moe.swiglu(x, lp["s_gate"], lp["s_up"],
                                   lp["s_down"])
    return h + rms_norm(y, lp["n4"], eps), load, pages


def _sparse_layer(cfg: LmConfig, geo: Geometry, st: _Step, li: int, lp: dict,
                  h: jax.Array, kv: dict):
    """One ``KeyeVL2`` layer; returns ``(h, expert load, (keys attended,
    keys a causal-dense layer would have) of the first layer)``."""
    chunk, page, plan = st.chunk, geo.page, st.plan
    nh, nkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    g, eps, theta = nh // nkv, cfg.rms_norm_eps, cfg.rope_theta
    ih, idim, top = cfg.index_heads, cfg.index_head_dim, cfg.index_topk
    blocks = dict(page=page, block_pages=geo.kv_block_pages)
    x = rms_norm(h, lp["n1"], eps)
    q = rms_norm(mm(x, lp["wq"]).reshape(-1, nh, hd), lp["qn"], eps)
    k = rms_norm(mm(x, lp["wk"]).reshape(-1, nkv, hd), lp["kn"], eps)
    v = mm(x, lp["wv"]).reshape(-1, nkv, hd)
    q = (rope(q, st.pos, theta) * (1.0 / math.sqrt(hd))).astype(
        BF16).reshape(-1, nkv, g, hd)
    k, v = rope(k, st.pos, theta).astype(BF16), v.astype(BF16)
    with jax.named_scope("lm.attn.index"):
        qi = rope(mm(x, lp["iq"]).reshape(-1, ih, idim), st.pos,
                  theta).astype(BF16)
        ki = rope(layer_norm(mm(x, lp["ik"]), lp["ikn"], lp["ikb"],
                             eps)[:, None, :], st.pos, theta)[:, 0].astype(
                                 BF16)
        wi = mm(x, lp["iw"]) * (ih ** -0.5 * idim ** -0.5)
    row_tab, zero = plan["row_ftab"], jnp.zeros_like(st.row_pos)
    ctab = plan["chunk_ftab"] if chunk else None
    with jax.named_scope("lm.cache.write"):
        pk, pv, pki = _write_pages(
            st, geo, [kv["k"][li], kv["v"][li], kv["ki"][li]], [k, v, ki],
            ctab, jnp.int32(0), row_tab, zero)
    kv["k"][li], kv["v"][li], kv["ki"][li] = pk, pv, pki

    # the rows: one query each; lax.top_k, then the chosen keys alone
    with jax.named_scope("lm.attn.index"):
        scores = index_scores(qi[chunk:, None], wi[chunk:, None],
                              st.row_pos[:, None], st.row_last, pki, row_tab,
                              width=geo.key_width, **blocks)
    with jax.named_scope("lm.attn.select"):
        positions, live = top_positions(scores[:, 0], top)
    with jax.named_scope("lm.attn.sparse"):
        o = gathered_attention(q[chunk:], positions, live, pk, pv, row_tab,
                               page=page)
    if chunk:
        # the chunk: every query its own set, as a mask over the pages
        # that all of them read
        with jax.named_scope("lm.attn.index"):
            scores = index_scores(qi[None, :chunk], wi[None, :chunk],
                                  (st.p0 + st.offs)[None],
                                  st.chunk_last[None], pki, ctab[None],
                                  width=geo.key_width, **blocks)
        with jax.named_scope("lm.attn.select"):
            chosen = select_keys(scores, top, st.p0 + st.n,
                                 block=geo.kv_block_pages * page)
        with jax.named_scope("lm.attn.sparse"):
            o_chunk, _ = paged_attention(
                q[None, :chunk], (st.p0 + st.offs)[None],
                st.chunk_last[None], pk, pv, ctab[None],
                jnp.zeros((1,), jnp.int32), window=None, chosen=chosen,
                **blocks)
        o = jnp.concatenate([o_chunk[0], o])
    keys = None
    if li == 0:
        causal = jnp.where(st.valid, st.pos + 1, 0)
        keys = jnp.stack([jnp.sum(jnp.minimum(causal, top)),
                          jnp.sum(causal)])
    h = h + mm(o.reshape(-1, nh * hd), lp["wo"])
    y, load = _experts(cfg, lp, rms_norm(h, lp["n2"], eps), st.valid)
    return h + y, load, keys


def build_step(cfg: LmConfig, geo: Geometry, chunk: int):
    """``step(params, kv, last_tok, plan) -> (kv, last_tok, out)`` for
    one bucket: ``chunk`` prefill tokens (0: none) beside ``geo.rows``
    decoding rows. ``out``: ``logits`` (rows + 1, V) float32, the rows'
    and, last, the chunk's last position's; ``ints`` one int32 vector
    (:func:`unpack_ints`): ``tokens`` (rows + 1,) their argmax,
    ``expert_load`` (expert layers, 3) the fullest expert's and all
    experts' valid tokens and the experts that hold any row, then
    ``pages`` (2,) pages one window layer visited and pages a
    causal-full layer would have or, for a model with an indexer,
    ``keys`` (2,) keys one layer attended and keys a causal-dense layer
    would have."""
    geo.check(cfg)
    r = geo.rows
    eps = cfg.rms_norm_eps
    layer = _sparse_layer if cfg.index_topk else _afmoe_layer

    def step(params, kv, last_tok, plan):
        row_pos = plan["row_pos"]
        row_on = plan["row_active"]
        st = _Step(chunk, plan, row_pos, row_on, row_pos, row_on,
                   jnp.where(row_on, row_pos, -1))
        if chunk:
            st.p0, st.n, chunk_row, st.cwbase = (plan["chunk_meta"][i]
                                                 for i in range(4))
            st.offs = jnp.arange(chunk, dtype=jnp.int32)
            ids = jnp.concatenate([plan["chunk_ids"], last_tok])
            st.pos = jnp.concatenate([st.p0 + st.offs, row_pos])
            st.valid = jnp.concatenate([st.offs < st.n, row_on])
        else:
            ids = last_tok
        with jax.named_scope("lm.embed"):
            h = params["embed"][ids].astype(F32)
            if cfg.mup_enabled:
                h = h * math.sqrt(cfg.hidden_size)

        new = {name: list(pools) for name, pools in kv.items()}
        loads = []
        read = jnp.zeros((2,), jnp.int32)
        for li, lp in enumerate(params["layers"]):
            h, load, counted = layer(cfg, geo, st, li, lp, h, new)
            if load is not None:
                loads.append(load)
            if counted is not None:
                read = counted

        with jax.named_scope("lm.head"):
            if chunk:
                top = jnp.concatenate([h[chunk:],
                                       h[jnp.maximum(st.n - 1, 0)][None]])
            else:
                top = jnp.concatenate([h, jnp.zeros_like(h[:1])])
            logits = mm(rms_norm(top, params["final_norm"], eps),
                        params["head"])
            tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        nxt = jnp.where(row_on, tokens[:r], last_tok)
        if chunk:
            nxt = nxt.at[jnp.where(chunk_row >= 0, chunk_row, r)].set(
                tokens[r], mode="drop")
        out = {"logits": logits, "ints": jnp.concatenate(
            [tokens] + [x.astype(jnp.int32) for x in loads] + [read])}
        return new, nxt, out

    step.__name__ = f"lm_step_c{chunk}"
    step.__qualname__ = step.__name__
    # the form the chunk's attention takes in this program (None: no chunk)
    step.attn_chunk_form = attention_form(
        1, chunk, cfg.num_key_value_heads, cfg.head_dim, geo.page) \
        if chunk else None
    return step
