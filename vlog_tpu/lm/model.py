"""The transcript model: configuration, layer mathematics, step program.

A decoder-only model of the published ``afmoe`` form. Every layer is
``h = h + N2(Attn(N1(h)))`` then ``h = h + N4(Mlp(N3(h)))`` (RMSNorm
before and after each block). Attention has 8 query heads a K/V head,
an RMSNorm over each head's dims of ``q`` and ``k``, rotary embedding on
``sliding_attention`` layers only (``full_attention`` layers carry no
positions), a band of ``sliding_window`` keys on the window layers, and
an output gate ``sigmoid(x Wg)`` on the concatenated heads. The first
``num_dense_layers`` MLPs are SwiGLU; the rest are the expert layer of
``moe.py``. The embedding is scaled by ``sqrt(hidden)`` (``mup_enabled``)
and the head is untied.

Precision as stated: weights and K/V bfloat16, products accumulate in
float32, the residual stream, norms, router, softmax and logits float32.

**The step program** (:func:`build_step`) serves one engine step: at
most one prefill chunk of ONE request (``chunk`` tokens, a static
bucket) beside every decoding row (``rows``, one token each). All of a
step's tokens pass the projections, the dense MLPs and the experts
together; attention runs per sequence over the paged cache: the chunk's
and the rows' new K/V are written into their pages first, then each
query block reads its sequence's pages ``kv_block_pages`` at a time
under an online softmax (never more than queries x block scores), from
the table's first page to the page of its last query. A table is a
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

from vlog_tpu.lm import moe

F32 = jnp.float32
BF16 = jnp.bfloat16
SLIDING = "sliding_attention"
MASKED = -1e30


@dataclass(frozen=True)
class LmConfig:
    """The model's shape, from a published ``config.json`` (HF keys)."""

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

    @classmethod
    def from_hf(cls, d: dict) -> "LmConfig":
        n = int(d["num_hidden_layers"])
        kinds = tuple(d["layer_types"][:n])
        if len(kinds) != n:
            raise ValueError("layer_types is shorter than num_hidden_layers")
        if d.get("score_func", "sigmoid") != "sigmoid" \
                or int(d.get("n_group", 1)) != 1 \
                or int(d.get("topk_group", 1)) != 1:
            raise ValueError("only sigmoid routing in one group is built")
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

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def window_layers(self) -> int:
        return sum(k == SLIDING for k in self.layer_types)

    @property
    def full_layers(self) -> int:
        return self.num_layers - self.window_layers


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
        """Pages a window table holds at most: the window plus one chunk."""
        return (window + self.chunk) // self.page

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

    def check(self, cfg: LmConfig) -> None:
        if self.chunk % self.page or cfg.sliding_window % self.page:
            raise ValueError("chunk and window must be whole pages")
        if self.context_cap % self.page:
            raise ValueError("context_cap must be whole pages")


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


def paged_attention(q: jax.Array, qpos: jax.Array, last_pos: jax.Array,
                    pool_k: jax.Array, pool_v: jax.Array, table: jax.Array,
                    base: jax.Array, *, window: int | None, page: int,
                    block_pages: int) -> tuple[jax.Array, jax.Array]:
    """Online-softmax attention of a batch of sequences over their pages.

    ``q`` (S, Q, nkv, g, hd) bfloat16, already scaled; ``qpos`` (S, Q)
    absolute positions of the queries; ``last_pos`` (S,) the last
    position that holds a key (-1: the sequence is absent); ``table``
    (S, W) physical pages from position ``base`` (S,) on. Returns
    ``(out (S, Q, nkv, g, hd) float32, pages visited (S,))``.
    """
    s, nq, nkv, g, hd = q.shape
    width = table.shape[1]
    keys = block_pages * page
    n_pages = jnp.where(last_pos >= 0, (last_pos - base) // page + 1, 0)
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
# the step program
# --------------------------------------------------------------------------

def empty_cache(cfg: LmConfig, geo: Geometry) -> dict:
    """Per layer one K and one V pool ``(pages, page, nkv, hd)``; the
    layers of a class share page numbers (``cache.py``)."""
    def pool(pages):
        return jnp.zeros((pages, geo.page, cfg.num_key_value_heads,
                          cfg.head_dim), BF16)

    sizes = [geo.window_pages if k == SLIDING else geo.full_pages
             for k in cfg.layer_types]
    return {"k": [pool(n) for n in sizes], "v": [pool(n) for n in sizes]}


def unpack_ints(cfg: LmConfig, geo: Geometry, ints) -> dict:
    """A step's ``out["ints"]`` (on the host) by name."""
    r = geo.rows + 1
    n_moe = cfg.num_layers - cfg.num_dense_layers
    return {"tokens": ints[:r],
            "expert_load": ints[r:r + 3 * n_moe].reshape(n_moe, 3),
            "pages": ints[r + 3 * n_moe:]}


def plan_shapes(cfg: LmConfig, geo: Geometry, chunk: int) -> dict:
    """``{name: (shape, dtype)}`` of the plan a step of this bucket
    takes (the host stacks it, ``engine.py``)."""
    ring, r = geo.ring(cfg.sliding_window), geo.rows
    out = {"row_active": ((r,), jnp.bool_), "row_pos": ((r,), jnp.int32),
           "row_wtab": ((r, ring), jnp.int32),
           "row_wbase": ((r,), jnp.int32),
           "row_ftab": ((r, geo.max_pages), jnp.int32)}
    if chunk:
        out.update({"chunk_ids": ((chunk,), jnp.int32),
                    # p0, n, row (-1: not the last chunk), window base
                    "chunk_meta": ((4,), jnp.int32),
                    "chunk_wtab": ((ring,), jnp.int32),
                    "chunk_ftab": ((geo.max_pages,), jnp.int32)})
    return out


def build_step(cfg: LmConfig, geo: Geometry, chunk: int):
    """``step(params, kv, last_tok, plan) -> (kv, last_tok, out)`` for
    one bucket: ``chunk`` prefill tokens (0: none) beside ``geo.rows``
    decoding rows. ``out``: ``logits`` (rows + 1, V) float32, the rows'
    and, last, the chunk's last position's; ``ints`` one int32 vector
    (:func:`unpack_ints`): ``tokens`` (rows + 1,) their argmax,
    ``expert_load`` (expert layers, 3) the fullest expert's and all
    experts' valid tokens and the experts that hold any row, ``pages`` (2,) pages one window layer visited
    and pages a causal-full layer would have."""
    geo.check(cfg)
    r, page = geo.rows, geo.page
    nh, nkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    g = nh // nkv
    eps = cfg.rms_norm_eps
    sm = 1.0 / math.sqrt(hd)

    def step(params, kv, last_tok, plan):
        row_pos = plan["row_pos"]
        row_on = plan["row_active"]
        if chunk:
            p0, n, chunk_row, cwbase = (plan["chunk_meta"][i]
                                        for i in range(4))
            offs = jnp.arange(chunk, dtype=jnp.int32)
            ids = jnp.concatenate([plan["chunk_ids"], last_tok])
            pos = jnp.concatenate([p0 + offs, row_pos])
            valid = jnp.concatenate([offs < n, row_on])
        else:
            ids, pos, valid = last_tok, row_pos, row_on
        with jax.named_scope("lm.embed"):
            h = params["embed"][ids].astype(F32)
            if cfg.mup_enabled:
                h = h * math.sqrt(cfg.hidden_size)

        row_last = jnp.where(row_on, row_pos, -1)
        new_k, new_v = list(kv["k"]), list(kv["v"])
        loads = []
        pages = jnp.zeros((2,), jnp.int32)
        for li, lp in enumerate(params["layers"]):
            is_window = cfg.layer_types[li] == SLIDING
            window = cfg.sliding_window if is_window else None
            x = rms_norm(h, lp["n1"], eps)
            q = rms_norm(mm(x, lp["wq"]).reshape(-1, nh, hd), lp["qn"], eps)
            k = rms_norm(mm(x, lp["wk"]).reshape(-1, nkv, hd), lp["kn"], eps)
            v = mm(x, lp["wv"]).reshape(-1, nkv, hd)
            if is_window:
                q = rope(q, pos, cfg.rope_theta)
                k = rope(k, pos, cfg.rope_theta)
            q = (q * sm).astype(BF16).reshape(-1, nkv, g, hd)
            k, v = k.astype(BF16), v.astype(BF16)
            if is_window:
                row_tab, row_base = plan["row_wtab"], plan["row_wbase"]
            else:
                row_tab, row_base = plan["row_ftab"], jnp.zeros_like(row_pos)
            pk, pv = new_k[li], new_v[li]
            with jax.named_scope("lm.cache.write"):
                if chunk:
                    ctab = plan["chunk_wtab"] if is_window \
                        else plan["chunk_ftab"]
                    cbase = cwbase if is_window else jnp.int32(0)
                    first = (p0 - cbase) // page
                    for j in range(chunk // page):
                        slot = jnp.minimum(first + j, ctab.shape[0] - 1)
                        phys = jnp.where(j * page < n, ctab[slot], 0)
                        at = (phys, 0, 0, 0)
                        pk = lax.dynamic_update_slice(
                            pk, k[None, j * page:(j + 1) * page], at)
                        pv = lax.dynamic_update_slice(
                            pv, v[None, j * page:(j + 1) * page], at)
                slot = jnp.clip((row_pos - row_base) // page, 0,
                                row_tab.shape[1] - 1)
                phys = jnp.where(row_on, jnp.take_along_axis(
                    row_tab, slot[:, None], axis=1)[:, 0], 0)
                pk = pk.at[phys, row_pos % page].set(k[chunk:])
                pv = pv.at[phys, row_pos % page].set(v[chunk:])
            new_k[li], new_v[li] = pk, pv
            with jax.named_scope("lm.attn.window" if is_window
                                 else "lm.attn.full"):
                o_rows, seen = paged_attention(
                    q[chunk:, None], row_pos[:, None], row_last, pk, pv,
                    row_tab, row_base, window=window, page=page,
                    block_pages=geo.kv_block_pages)
                o = o_rows[:, 0]
                would = jnp.where(row_on, row_pos // page + 1, 0)
                if chunk:
                    o_chunk, c_seen = paged_attention(
                        q[None, :chunk], (p0 + offs)[None],
                        jnp.where(n > 0, p0 + n - 1, -1)[None], pk, pv,
                        ctab[None], cbase[None], window=window, page=page,
                        block_pages=geo.kv_block_pages)
                    o = jnp.concatenate([o_chunk[0], o])
                    seen = jnp.concatenate([seen, c_seen])
                    would = jnp.concatenate([would, jnp.where(
                        n > 0, (p0 + n - 1) // page + 1, 0)[None]])
                if is_window and li == cfg.layer_types.index(SLIDING):
                    pages = jnp.stack([jnp.sum(seen), jnp.sum(would)])
            with jax.named_scope("lm.attn.gate"):
                o = o.reshape(-1, nh * hd) * jax.nn.sigmoid(mm(x, lp["wg"]))
            h = h + rms_norm(mm(o, lp["wo"]), lp["n2"], eps)

            x = rms_norm(h, lp["n3"], eps)
            if li < cfg.num_dense_layers:
                with jax.named_scope("lm.mlp.dense"):
                    y = moe.swiglu(x, lp["w_gate"], lp["w_up"],
                                   lp["w_down"])
            else:
                chosen, weights, _ = moe.route(
                    x, lp["router"], lp["bias"],
                    top_k=cfg.num_experts_per_tok,
                    route_norm=cfg.route_norm, route_scale=cfg.route_scale)
                y, counted, held = moe.experts(
                    x, chosen, weights, lp["e_gate"], lp["e_up"],
                    lp["e_down"], valid)
                loads.append(jnp.stack([jnp.max(counted),
                                        jnp.sum(counted), held]))
                if cfg.num_shared_experts:
                    with jax.named_scope("lm.moe.shared"):
                        y = y + moe.swiglu(x, lp["s_gate"], lp["s_up"],
                                           lp["s_down"])
            h = h + rms_norm(y, lp["n4"], eps)

        with jax.named_scope("lm.head"):
            if chunk:
                top = jnp.concatenate([h[chunk:],
                                       h[jnp.maximum(n - 1, 0)][None]])
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
            [tokens] + [x.astype(jnp.int32) for x in loads] + [pages])}
        return {"k": new_k, "v": new_v}, nxt, out

    step.__name__ = f"lm_step_c{chunk}"
    step.__qualname__ = step.__name__
    return step
