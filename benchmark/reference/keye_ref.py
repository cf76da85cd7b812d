"""Plain reference of Keye-VL-2.0's language model (``KeyeVL2``): the
full forward pass of ONE sequence in float32, with no cache, no paging,
no batching and no kernel.

It holds the same bfloat16 weight values as the program, upcasts a
layer (and one expert) at a time, computes under
``jax.default_matmul_precision("highest")`` (every product and sum in
float32), rounds an activation to bfloat16 exactly where the
configuration states a bfloat16 operand (below), and runs every stage that
holds a product on blocks of ``ROWS`` rows against keys from a short
list of lengths, so that a 37k-token sequence fits and a handful of
programs compile (``afmoe_ref.py`` says why). It imports nothing of the
program. What the published config does not fix is a keyword of
:func:`forward` (``MECHANISMS``; ``select``), so that a test or a
control can leave each out and see the comparison fail. ``compute`` is
the control's: the same pass with everything the configuration states
as float32 in another dtype.

**The stated precision.** The configuration states bfloat16 for the
weights, for K, V and the indexer's keys, and for the operands of every
product (float32 accumulation); the residual stream, norms, router,
index scores, softmax and logits are float32. This model's forward pass
holds two hard choices a layer (8 of 128 experts; 2,048 of up to 37,000
keys, whose neighbouring index scores lie 1e-5 apart), so a pass that
kept the operands in float32 would choose other keys than ANY bfloat16
program on every long query and measure that, not the program (my chip
runs, PR 33: 0.14 to 0.18 of the logits' spread at the median of a
talk's positions). So ``operands`` (default ``bfloat16``) rounds the
activations that enter a product (the normed input of the projections,
q, k, v, the indexer's query and key, the heads before ``Wo``, an
expert's input and hidden, the head's input) to that dtype and back;
``float32`` leaves them alone (the CPU tests, where the program runs in
float32 too). Sums, norms, the router, ReLU, softmax and the choices
stay float32 either way.

Layer (every one of them): ``x = N1(h)``; ``q = Nq(x Wq)`` as 32 heads,
``k = Nk(x Wk)`` and ``v = x Wv`` as 4 (head ``i`` reads K/V head
``i // 8``), rotate-half rotary on q and k; the indexer ``qI = x WqI``
as 16 heads of 64, ``kI = LayerNorm(x WkI)`` one head of 64, both
rotated, ``w = (x Ww) / sqrt(16 * 64)``; ``I[t, s] = sum_j w[t, j]
relu(qI[t, j] . kI[s])`` for ``s <= t``; query ``t`` attends the
``topk`` positions of largest ``I[t, .]`` (``lax.top_k``: ties to the
lower position; all of them while it has no more); ``h = h + heads Wo``;
``x2 = N2(h)``, ``p = softmax(x2 Wr)``, top 8, weights ``p`` at the
chosen over their sum, ``h = h + sum g_e expert_e(x2)``. A final RMSNorm
and an untied head.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
# what the config's keys do not fix (ISSUE 33, ``assumed``); each can be
# switched off to show that the comparison notices
MECHANISMS = ("head_norms", "rope", "index_rope", "index_key_norm",
              "index_relu", "index_weights", "route_norm")
# which keys a query with more than ``topk`` causal keys attends: the
# learned choice, every causal key (no selection at all), the newest, or
# the learned choice with its last key swapped for the best one left out
# (what ONE near-tie that falls the other way, in every layer, moves)
SELECT = ("learned", "dense", "newest", "swapped")
ROWS = 256          # rows a block: queries of an attention block too
K_BUCKET = 8192


def _op(x, operands):
    """An activation as a product's operand: rounded to the stated
    dtype, in the dtype it came in."""
    return x if operands is None else x.astype(operands).astype(x.dtype)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(x.dtype)


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w.astype(x.dtype) \
        + b.astype(x.dtype)


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos.astype(F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _blocks(fn, *rows, **kw):
    """``fn`` over lists of row blocks; returns a tuple of lists."""
    outs = [fn(*args, **kw) for args in zip(*rows)]
    return list(zip(*outs)) if isinstance(outs[0], tuple) else outs


@partial(jax.jit, static_argnames=("dims", "on", "theta", "operands"))
def _project(h, p0, lp, *, dims, on, theta, operands):
    """One block of rows from position ``p0`` on: everything attention
    needs of the normed input, rotated; q already over sqrt(head_dim)."""
    nh, nkv, hd, ih, idim, eps = dims
    head_norms, rope, index_rope, key_norm = on
    pos = p0 + jnp.arange(h.shape[0])
    x = _op(_rms(h, lp["n1"], eps), operands)
    dt = x.dtype
    q = (x @ lp["wq"].astype(dt)).reshape(-1, nh, hd)
    k = (x @ lp["wk"].astype(dt)).reshape(-1, nkv, hd)
    v = (x @ lp["wv"].astype(dt)).reshape(-1, nkv, hd)
    if head_norms:
        q, k = _rms(q, lp["qn"], eps), _rms(k, lp["kn"], eps)
    if rope:
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    qi = (x @ lp["iq"].astype(dt)).reshape(-1, ih, idim)
    ki = x @ lp["ik"].astype(dt)
    if key_norm:
        ki = _layer_norm(ki, lp["ikn"], lp["ikb"], eps)
    ki = ki[:, None, :]
    if index_rope:
        qi, ki = _rope(qi, pos, theta), _rope(ki, pos, theta)
    wi = (x @ lp["iw"].astype(dt)) * (ih ** -0.5 * idim ** -0.5)
    q = q * (1.0 / math.sqrt(hd))
    return tuple(_op(a, operands) for a in (q, k, v, qi, ki[:, 0])) + (wi,)


@partial(jax.jit, static_argnames=("klen",))
def _keys(k, v, ki, *, klen):
    return k[:klen], v[:klen], ki[:klen]


@partial(jax.jit, static_argnames=("top", "select", "relu", "weights"))
def _attend(qb, kb, vb, qib, wib, kib, q0, *, top, select, relu, weights):
    """One block of queries from position ``q0`` against the keys from
    position 0 on. Returns ``(heads' outputs, the margin by which each
    query's selection stands: its ``top``-th index score over the next,
    ``inf`` where it has no more than ``top`` keys)``."""
    nq, klen = qb.shape[0], kb.shape[0]
    qp = q0 + jnp.arange(nq)[:, None]
    kp = jnp.arange(klen)[None, :]
    causal = kp <= qp
    dots = jnp.einsum("qjd,kd->qjk", qib, kib)
    if relu:
        dots = jnp.maximum(dots, 0.0)
    if weights:
        dots = dots * wib[:, :, None]
    index = jnp.where(causal, jnp.sum(dots, axis=1).astype(F32), -jnp.inf)
    margin = jnp.full((nq,), jnp.inf, F32)
    if select == "dense" or klen <= top:
        ok = causal
    elif select == "newest":
        ok = causal & (kp > qp - top)
    else:
        vals, idx = jax.lax.top_k(index, top + 1)
        kept = idx[:, :top] if select == "learned" else jnp.concatenate(
            [idx[:, :top - 1], idx[:, top:]], axis=1)
        ok = jnp.zeros((nq, klen), bool).at[
            jnp.arange(nq)[:, None], kept].set(True) & causal
        margin = jnp.where(qp[:, 0] + 1 > top,
                           vals[:, top - 1] - vals[:, top], jnp.inf)
    rep = qb.shape[1] // kb.shape[1]
    kb = jnp.repeat(kb, rep, axis=1)
    vb = jnp.repeat(vb, rep, axis=1)
    s = jnp.einsum("qhd,khd->hqk", qb, kb)
    s = jnp.where(ok[None], s, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), vb), margin


def _attention(blocks, *, top, select, relu, weights):
    """Each block of ``ROWS`` queries against the keys up to the next
    multiple of ``K_BUCKET``."""
    q, k, v, qi, ki, wi = blocks
    t = len(q) * ROWS
    pad = -t % K_BUCKET

    def whole(parts):
        return jnp.concatenate(list(parts) + [jnp.zeros(
            (pad,) + parts[0].shape[1:], parts[0].dtype)])

    k, v, ki = whole(k), whole(v), whole(ki)
    outs, margins = [], []
    for i, (qb, qib, wib) in enumerate(zip(q, qi, wi)):
        q1 = (i + 1) * ROWS
        kb, vb, kib = _keys(k, v, ki, klen=q1 + (-q1 % K_BUCKET))
        o, m = _attend(qb, kb, vb, qib, wib, kib, jnp.int32(i * ROWS),
                       top=top, select=select, relu=relu, weights=weights)
        outs.append(o)
        margins.append(m)
    return outs, jnp.concatenate(margins)


@partial(jax.jit, static_argnames=("eps", "operands"))
def _after_attention(h, o, wo, n2, *, eps, operands):
    h = h + _op(o.reshape(h.shape[0], -1), operands) @ wo.astype(h.dtype)
    return h, _rms(h, n2, eps)


def _swiglu(x, gate, up, down, operands):
    dt = x.dtype
    x = _op(x, operands)
    hidden = jax.nn.silu(x @ gate.astype(dt)) * (x @ up.astype(dt))
    return _op(hidden, operands) @ down.astype(dt)


@partial(jax.jit, static_argnames=("k", "norm"))
def _route(x, router, *, k, norm):
    p = jax.nn.softmax(x @ router.astype(x.dtype), axis=-1)
    top, chosen = jax.lax.top_k(p, k + 1)
    w = top[:, :k]
    if norm:
        w = w / jnp.sum(w, -1, keepdims=True)
    # the margin by which the choice stands: k-th over (k+1)-th
    return chosen[:, :k], w, top[:, k - 1] - top[:, k]


@partial(jax.jit, static_argnames=("operands",))
def _expert_rows(xg, wt, gate, up, down, e, *, operands):
    """Expert ``e`` (a traced index into the stacked weights) on the
    gathered rows ``xg``, weighted by ``wt``."""
    pick = lambda w: jax.lax.dynamic_index_in_dim(w, e, keepdims=False)  # noqa: E731
    return _swiglu(xg, pick(gate), pick(up), pick(down), operands) \
        * wt.astype(xg.dtype)[:, None]


_gather = jax.jit(lambda x, idx: x[idx])
_scatter_add = jax.jit(lambda y, idx, ye: y.at[idx].add(ye))
_add = jax.jit(lambda h, y: h + y)


def _bucket(n: int) -> int:
    b = 256
    while b < n:
        b *= 4
    return b


def _experts(x_blocks, lp, cfg, norm, operands):
    """Returns (y blocks, gaps): gaps (T,) the margin by which each
    token's top-k choice stands."""
    chosen, w, gaps = _blocks(_route, x_blocks, router=lp["router"],
                              k=cfg["num_experts_per_tok"], norm=norm)
    chosen_h = np.concatenate([np.asarray(c) for c in chosen])
    w_h = np.concatenate([np.asarray(a, np.float32) for a in w])
    x = jnp.concatenate(x_blocks)
    y = jnp.zeros_like(x)
    for e in range(cfg["num_experts"]):
        rows, slots = np.nonzero(chosen_h == e)
        if not rows.size:
            continue
        pad = _bucket(rows.size) - rows.size
        idx = np.concatenate([rows, np.zeros(pad, rows.dtype)]).astype(
            np.int32)
        wt = np.concatenate([w_h[rows, slots], np.zeros(pad, np.float32)])
        ye = _expert_rows(_gather(x, idx), wt, lp["e_gate"], lp["e_up"],
                          lp["e_down"], np.int32(e), operands=operands)
        y = _scatter_add(y, idx, ye)
    return list(jnp.split(y, len(x_blocks))), jnp.concatenate(gaps)


@partial(jax.jit, static_argnames=("compute",))
def _embed(table, ids, *, compute):
    return table[ids].astype(compute)


@partial(jax.jit, static_argnames=("eps", "operands"))
def _head(top, norm, head, *, eps, operands):
    return _op(_rms(top, norm, eps), operands) @ head.astype(top.dtype)


def forward(params: dict, cfg: dict, ids, positions, *,
            off: tuple[str, ...] = (), select: str = "learned",
            operands: str = "bfloat16", compute=F32) -> dict:
    """The whole sequence ``ids`` (T,) through the ``num_hidden_layers``
    layers of ``cfg`` (the configuration file's dict, HF keys). Returns
    ``logits`` (len(positions), V) float32 at the asked positions,
    ``route_gap`` and ``select_gap`` (len(positions),): the smallest
    margin, over the layers, by which a position's top-8 experts and
    its ``topk`` keys stand (``inf`` where it attends every key).
    ``operands``: the dtype of a product's activations (module
    docstring)."""
    unknown = set(off) - set(MECHANISMS)
    if unknown or select not in SELECT \
            or operands not in ("bfloat16", "float32"):
        raise ValueError(f"unknown mechanisms {sorted(unknown)}, selection "
                         f"{select!r} or operands {operands!r}")
    operands = jnp.bfloat16 if operands == "bfloat16" else None
    on = {m: m not in off for m in MECHANISMS}
    sa = cfg["sa_config"]
    eps = float(cfg["rms_norm_eps"])
    dims = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], sa["indexer_num_heads"],
            sa["indexer_head_dim"], eps)
    ids = np.asarray(ids, np.int32)
    ids = np.concatenate([ids, np.zeros(-ids.size % ROWS, np.int32)])
    positions = jnp.asarray(np.asarray(positions), jnp.int32)
    n_blocks = ids.size // ROWS
    starts = [jnp.int32(i * ROWS) for i in range(n_blocks)]
    with jax.default_matmul_precision("highest"):
        h = [_embed(params["embed"], blk, compute=compute)
             for blk in np.split(ids, n_blocks)]
        route_gap = select_gap = None
        for li in range(cfg["num_hidden_layers"]):
            lp = params["layers"][li]
            attn = {k: lp[k] for k in ("n1", "wq", "wk", "wv", "qn", "kn",
                                       "iq", "ik", "ikn", "ikb", "iw")}
            projected = _blocks(
                _project, h, starts, lp=attn, dims=dims,
                on=(on["head_norms"], on["rope"], on["index_rope"],
                    on["index_key_norm"]),
                theta=float(cfg["rope_theta"]), operands=operands)
            o, margins = _attention(
                projected, top=int(sa["topk"]), select=select,
                relu=on["index_relu"], weights=on["index_weights"])
            h, x = _blocks(_after_attention, h, o, wo=lp["wo"], n2=lp["n2"],
                           eps=eps, operands=operands)
            y, gaps = _experts(x, lp, cfg, bool(
                on["route_norm"] and cfg.get("norm_topk_prob", True)),
                operands)
            h = _blocks(_add, h, y)
            at, sel = gaps[positions], margins[positions]
            route_gap = at if route_gap is None \
                else jnp.minimum(route_gap, at)
            select_gap = sel if select_gap is None \
                else jnp.minimum(select_gap, sel)
        top = jnp.concatenate(h)[positions]
        logits = _head(top, params["final_norm"], params["head"], eps=eps,
                       operands=operands)
    return {"logits": np.asarray(logits, np.float32),
            "route_gap": np.asarray(route_gap, np.float32),
            "select_gap": np.asarray(select_gap, np.float32)}


def logit_error(served: np.ndarray, ref: np.ndarray) -> float:
    """Largest difference of two logit rows over the reference's spread
    (its standard deviation over the vocabulary)."""
    return float(np.max(np.abs(served - ref)) / (np.std(ref) + 1e-30))


def rank_gap(served_token: int, ref: np.ndarray) -> float:
    """How far the served token's logit lies below the reference's
    best. A greedy step that agrees reads 0."""
    return float(max(0.0, np.max(ref) - ref[served_token]))
