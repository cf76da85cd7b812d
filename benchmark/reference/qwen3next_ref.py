"""Plain reference of Qwen3-Next-80B-A3B (``qwen3_next``): the full
forward pass of ONE sequence in float32, with no cache, no paging, no
batching, no chunking and no kernel.

It holds the same bfloat16 weight values as the program, upcasts them
where they are used, computes under ``jax.default_matmul_precision(
"highest")`` (a float32 product on the TPU is otherwise one bfloat16
pass), and runs every stage that holds a product on blocks of ``ROWS``
rows, attention on blocks of queries against keys from a short list of
lengths, so that a 37k-token sequence fits and a handful of programs
compile (``afmoe_ref.py`` says why). It imports nothing of the program.

Every layer is ``h = h + Mix(N1(h))`` then ``h = h + Moe(N2(h))``, where
``N(x) = x / sqrt(mean(x^2) + eps) * (1 + w)`` (zero-centred, as the
published ``Qwen3NextRMSNorm``), and the final norm is the same.

**Gated DeltaNet** (layers ``i`` with ``(i + 1) % 4 != 0``), ``x =
N1(h)``: ``[q k v z] = x Wqkvz`` read per key head as ``q`` (dk), ``k``
(dk), ``v`` (r dv), ``z`` (r dv) with ``r`` value heads a key head;
``[b a] = x Wba`` per key head as ``b`` (r), ``a`` (r); a causal
depthwise conv of 4 taps with no bias over the channels ``[q k v]`` of
the whole sequence (``out[t] = sum_m w[3 - m] x[t - m]``, zeros before
position 0), then SiLU; q and k L2-normalised per head (``x /
sqrt(sum x^2 + 1e-6)``), q times ``dk^-0.5``, each key head repeated for
its ``r`` value heads; ``beta = sigmoid(b)``, ``g = -exp(A_log)
softplus(a + dt_bias)``; then, position by position (a ``lax.scan`` over
the sequence: the recurrence itself, not a chunked form), per value head
with ``S`` (dk x dv) from zeros: ``S = e^g S``, ``S = S + k ((v - S^T k)
beta)^T``, ``o = S^T q``; ``y = o / sqrt(mean(o^2) + eps) * w * SiLU(z)``
per head (the gated norm: NOT zero-centred), then ``Wout``.

**Gated attention** (every fourth layer): ``x Wq`` read per head as
``[q | gate]`` (hd each); ``q = N(q)``, ``k = N(x Wk)`` per head (zero-
centred); rotate-half rotary over the first ``head_dim *
partial_rotary_factor`` dims at ``rope_theta``; each K/V head serves
``heads / kv heads`` query heads; ``softmax(q k^T hd^-0.5) v`` over ``j
<= t``; times ``sigmoid(gate)``; ``Wo``.

**Experts** (every layer): ``p = softmax(x Wr)`` over ALL the router's
outputs, the top k by ``p``, their weights over their sum; only the
experts held here (``first_held_expert`` on, ``num_experts`` of them)
are computed, the pairs routed to the others add nothing (the chip's
share, as the program); plus ``sigmoid(x Wsg) * Shared(x)``.

Departures from the published code, each at its line: the checkpoint's
``in_proj_qkvz`` / ``in_proj_ba`` columns are read grouped by key head
as ``fix_query_key_value_ordering`` reads them; multi-token prediction
is not computed (the main model's logits do not depend on it); only the
held share of the experts is computed. What the config does not fix is
marked ``(A)``.

The keywords are the controls': ``compute`` (the same pass with every
activation, the state, norms, router, softmax and logits in another
dtype), ``reset_state`` (the state zeroed at every prefill chunk's
first position), ``decay=False`` (``g = 0``), ``conv_carry=False`` (the
conv window starting afresh at every prefill chunk and at every output
position) and ``shared_gate=False`` (the shared expert added ungated).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
ROWS = 256          # rows a block: queries of an attention block too
K_BUCKET = 8192


def _norm0(x, w, eps):
    """The zero-centred RMSNorm: the factor is ``1 + w``."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w.astype(x.dtype))


def _blocks(fn, *rows, **kw):
    outs = [fn(*args, **kw) for args in zip(*rows)]
    return list(zip(*outs)) if isinstance(outs[0], tuple) else outs


@partial(jax.jit, static_argnames=("eps",))
def _norm(h, w, *, eps):
    return _norm0(h, w, eps)


# ---- Gated DeltaNet --------------------------------------------------------

@partial(jax.jit, static_argnames=("dims",))
def _gdn_in(x, w_qkvz, w_ba, *, dims):
    """One block's projections: ``(conv input [q k v] (rows, C), z
    (rows, nv, dv), b, a (rows, nv))``, read per key head (the published
    ``fix_query_key_value_ordering``)."""
    nk, nv, dk, dv = dims
    r, t = nv // nk, x.shape[0]
    dt = x.dtype
    qkvz = (x @ w_qkvz.astype(dt)).reshape(t, nk, 2 * dk + 2 * r * dv)
    q, k = qkvz[..., :dk], qkvz[..., dk:2 * dk]
    v = qkvz[..., 2 * dk:2 * dk + r * dv]
    z = qkvz[..., 2 * dk + r * dv:].reshape(t, nv, dv)
    ba = (x @ w_ba.astype(dt)).reshape(t, nk, 2 * r)
    return (jnp.concatenate([q.reshape(t, -1), k.reshape(t, -1),
                             v.reshape(t, -1)], -1),
            z, ba[..., :r].reshape(t, nv), ba[..., r:].reshape(t, nv))


@jax.jit
def _conv(xb, halo, w, back):
    """The causal conv of one block: ``halo`` the block before's last
    ``taps - 1`` rows, ``w`` (taps, C) (the published ``(C, 1, taps)``
    read as ``w[j] = conv1d.weight[:, 0, j]``), ``back`` (rows,) how many
    earlier positions each position's window may reach (3 everywhere
    but where a control restarts it); then SiLU."""
    taps = w.shape[0]
    xp = jnp.concatenate([halo, xb])
    rows = xb.shape[0]
    out = jnp.zeros_like(xb)
    for m in range(taps):               # x[t - m] meets w[taps - 1 - m]
        part = xp[taps - 1 - m:taps - 1 - m + rows]
        out = out + jnp.where((back >= m)[:, None], part, 0.0) \
            * w[taps - 1 - m].astype(xb.dtype)
    return jax.nn.silu(out)


@partial(jax.jit, static_argnames=("dims", "decay"))
def _gdn_qkvgb(y, b, a, a_log, dt_bias, *, dims, decay):
    nk, nv, dk, dv = dims
    r, t = nv // nk, y.shape[0]
    kd = nk * dk

    def l2(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    q = jnp.repeat(l2(y[:, :kd].reshape(t, nk, dk)) * dk ** -0.5, r, 1)
    k = jnp.repeat(l2(y[:, kd:2 * kd].reshape(t, nk, dk)), r, 1)
    v = y[:, 2 * kd:].reshape(t, nv, dv)
    g = -jnp.exp(a_log.astype(y.dtype)) * jax.nn.softplus(
        a + dt_bias.astype(y.dtype))
    if not decay:                       # a control: nothing is forgotten
        g = jnp.zeros_like(g)
    return q, k, v, g, jax.nn.sigmoid(b)


@jax.jit
def _recurrence(s, q, k, v, g, beta, reset):
    """The gated delta rule position by position over one block: ``s``
    (nv, dk, dv) carried in, ``reset`` (rows,) zeroes it first (a
    control)."""
    def step(s, xs):
        qt, kt, vt, gt, bt, rt = xs
        s = jnp.where(rt, jnp.zeros_like(s), s) * jnp.exp(gt)[:, None, None]
        remembered = jnp.einsum("hkv,hk->hv", s, kt)
        s = s + kt[:, :, None] * ((vt - remembered) * bt[:, None])[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, qt)

    return jax.lax.scan(step, s, (q, k, v, g, beta, reset))


@partial(jax.jit, static_argnames=("eps",))
def _gdn_out(o, z, norm_w, w_out, *, eps):
    """The gated norm (weight as published, NOT zero-centred), then
    ``Wout``."""
    y = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) \
        * norm_w.astype(o.dtype) * jax.nn.silu(z)
    return y.reshape(o.shape[0], -1) @ w_out.astype(o.dtype)


def _gdn(x_blocks, lp, cfg, *, back, reset, decay):
    eps = float(cfg["rms_norm_eps"])
    nk, nv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    dims = (nk, nv, dk, dv)
    mixed, z, b, a = _blocks(_gdn_in, x_blocks, w_qkvz=lp["w_qkvz"],
                             w_ba=lp["w_ba"], dims=dims)
    taps = lp["conv"].shape[0]
    dt = x_blocks[0].dtype
    halo = jnp.zeros((taps - 1, mixed[0].shape[1]), dt)
    s = jnp.zeros((nv, dk, dv), dt)
    out = []
    for i, xb in enumerate(mixed):
        y = _conv(xb, halo, lp["conv"], back[i])
        halo = xb[-(taps - 1):]
        q, k, v, g, beta = _gdn_qkvgb(y, b[i], a[i], lp["a_log"],
                                      lp["dt_bias"], dims=dims,
                                      decay=bool(decay))
        s, o = _recurrence(s, q, k, v, g, beta, reset[i])
        out.append(_gdn_out(o, z[i], lp["norm"], lp["w_out"], eps=eps))
    return out


# ---- gated attention -------------------------------------------------------

def _rope(x, pos, theta, dims):
    """Rotate-half rotary over the first ``dims`` of the head."""
    inv = theta ** (-jnp.arange(0, dims, 2, dtype=F32) / dims)
    ang = pos.astype(F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    rot = x[..., :dims]
    x1, x2 = rot[..., :dims // 2], rot[..., dims // 2:]
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    return jnp.concatenate([rot * cos + jnp.concatenate([-x2, x1], -1) * sin,
                            x[..., dims:]], -1)


@partial(jax.jit, static_argnames=("dims",))
def _attn_in(x, p0, wq, wk, wv, qn, kn, *, dims):
    nh, nkv, hd, rot, theta, eps = dims
    t = x.shape[0]
    dt = x.dtype
    pos = p0 + jnp.arange(t)
    qg = (x @ wq.astype(dt)).reshape(t, nh, 2 * hd)
    q = _rope(_norm0(qg[..., :hd], qn, eps), pos, theta, rot)
    k = _rope(_norm0((x @ wk.astype(dt)).reshape(t, nkv, hd), kn, eps), pos,
              theta, rot)
    v = (x @ wv.astype(dt)).reshape(t, nkv, hd)
    return q, qg[..., hd:].reshape(t, nh * hd), k, v


@partial(jax.jit, static_argnames=("klen",))
def _keys(k, v, *, klen):
    return k[:klen], v[:klen]


@partial(jax.jit, static_argnames=("scale",))
def _attend(qb, kb, vb, q0, *, scale):
    """One block of queries from ``q0`` against keys from 0; a K/V head
    serves ``heads / kv heads`` consecutive query heads."""
    t, nh, hd = qb.shape
    nkv = kb.shape[1]
    qg = qb.reshape(t, nkv, nh // nkv, hd)
    s = jnp.einsum("qngd,knd->ngqk", qg, kb) * scale
    qp = q0 + jnp.arange(t)[:, None]
    kp = jnp.arange(kb.shape[0])[None, :]
    s = jnp.where((kp <= qp)[None, None], s, -jnp.inf)
    o = jnp.einsum("ngqk,knd->qngd", jax.nn.softmax(s, axis=-1), vb)
    return o.reshape(t, nh * hd)


@jax.jit
def _attn_out(o, gate, wo):
    return (o * jax.nn.sigmoid(gate)) @ wo.astype(o.dtype)


def _attention(x_blocks, lp, cfg, starts):
    hd = cfg["head_dim"]
    dims = (cfg["num_attention_heads"], cfg["num_key_value_heads"], hd,
            int(hd * cfg["partial_rotary_factor"]), float(cfg["rope_theta"]),
            float(cfg["rms_norm_eps"]))
    q, gate, k, v = _blocks(_attn_in, x_blocks, starts, wq=lp["wq"],
                            wk=lp["wk"], wv=lp["wv"], qn=lp["qn"],
                            kn=lp["kn"], dims=dims)
    t = len(q) * ROWS
    pad = -t % K_BUCKET
    kk = jnp.concatenate(list(k) + [jnp.zeros((pad,) + k[0].shape[1:],
                                              k[0].dtype)])
    vv = jnp.concatenate(list(v) + [jnp.zeros((pad,) + v[0].shape[1:],
                                              v[0].dtype)])
    out = []
    for i, qb in enumerate(q):
        q1 = (i + 1) * ROWS
        kb, vb = _keys(kk, vv, klen=q1 + (-q1 % K_BUCKET))
        o = _attend(qb, kb, vb, jnp.int32(i * ROWS), scale=hd ** -0.5)
        out.append(_attn_out(o, gate[i], lp["wo"]))
    return out


# ---- experts -----------------------------------------------------------------

@jax.jit
def _swiglu(x, gate, up, down):
    dt = x.dtype
    return (jax.nn.silu(x @ gate.astype(dt)) * (x @ up.astype(dt))) \
        @ down.astype(dt)


@partial(jax.jit, static_argnames=("k", "norm"))
def _route(x, router, *, k, norm):
    logits = x @ router.astype(x.dtype)
    p = jax.nn.softmax(logits, axis=-1)
    top, chosen = jax.lax.top_k(logits, k + 1)   # softmax keeps the order
    w = jnp.take_along_axis(p, chosen[:, :k], -1)
    if norm:
        w = w / jnp.sum(w, -1, keepdims=True)
    # the margin by which the choice stands, in logits: k-th over (k+1)-th
    return chosen[:, :k], w, top[:, k - 1] - top[:, k]


@jax.jit
def _expert_rows(xg, wt, gate, up, down, e):
    pick = lambda w: jax.lax.dynamic_index_in_dim(w, e, keepdims=False)  # noqa: E731
    return _swiglu(xg, pick(gate), pick(up), pick(down)) \
        * wt.astype(xg.dtype)[:, None]


@partial(jax.jit, static_argnames=("gated",))
def _shared(x, gate, up, down, sg, *, gated):
    y = _swiglu(x, gate, up, down)
    return y * jax.nn.sigmoid(x @ sg.astype(x.dtype)) if gated else y


_gather = jax.jit(lambda x, idx: x[idx])
_scatter_add = jax.jit(lambda y, idx, ye: y.at[idx].add(ye))


def _bucket(n: int) -> int:
    b = 256
    while b < n:
        b *= 4
    return b


def _experts(x_blocks, lp, cfg, *, shared_gate):
    """Returns (y blocks, gaps): gaps (T,) the router margin of each
    token."""
    chosen, w, gaps = _blocks(_route, x_blocks, router=lp["router"],
                              k=cfg["num_experts_per_tok"],
                              norm=bool(cfg["norm_topk_prob"]))
    chosen_h = np.concatenate([np.asarray(c) for c in chosen])
    w_h = np.concatenate([np.asarray(a, np.float32) for a in w])
    x = jnp.concatenate(x_blocks)
    y = jnp.zeros_like(x)
    first = int(cfg.get("first_held_expert", 0))
    # the held share alone: pairs routed to the other experts add nothing
    for e in range(first, first + cfg["num_experts"]):
        rows, slots = np.nonzero(chosen_h == e)
        if not rows.size:
            continue
        pad = _bucket(rows.size) - rows.size
        idx = np.concatenate([rows, np.zeros(pad, rows.dtype)]).astype(
            np.int32)
        wt = np.concatenate([w_h[rows, slots], np.zeros(pad, np.float32)])
        ye = _expert_rows(_gather(x, idx), wt, lp["e_gate"], lp["e_up"],
                          lp["e_down"], np.int32(e - first))
        y = _scatter_add(y, idx, ye)
    shared = _blocks(_shared, x_blocks, gate=lp["s_gate"], up=lp["s_up"],
                     down=lp["s_down"], sg=lp["sg"], gated=bool(shared_gate))
    return [a + b for a, b in zip(jnp.split(y, len(x_blocks)), shared)], \
        jnp.concatenate(gaps)


# ---- the pass ------------------------------------------------------------------

@partial(jax.jit, static_argnames=("compute",))
def _embed(table, ids, *, compute):
    return table[ids].astype(compute)


@partial(jax.jit, static_argnames=("eps",))
def _head(top, norm, head, *, eps):
    return _norm0(top, norm, eps) @ head.astype(top.dtype)


def layer_kinds(cfg: dict) -> list[str]:
    every = int(cfg.get("full_attention_interval", 4))
    return [("full_attention" if (i + 1) % every == 0 else "linear_attention")
            for i in range(cfg["num_hidden_layers"])]


def forward(params: dict, cfg: dict, ids, positions, *, compute=F32,
            prompt: int | None = None, chunk: int = 2048,
            reset_state: bool = False, decay: bool = True,
            conv_carry: bool = True, shared_gate: bool = True) -> dict:
    """The whole sequence ``ids`` (T,) through the ``num_hidden_layers``
    layers of ``cfg`` (the configuration file's dict, HF keys). Returns
    ``logits`` (len(positions), V) float32 at the asked positions and
    ``route_gap`` (len(positions),): the smallest margin, over the
    layers, by which a position's top-k choice stands, in router logits.
    ``prompt`` (its length) and ``chunk`` place the prefill chunks for
    the controls ``reset_state`` and ``conv_carry`` (module
    docstring)."""
    eps = float(cfg["rms_norm_eps"])
    ids = np.asarray(ids, np.int32)
    t_real = ids.size
    prompt = t_real if prompt is None else int(prompt)
    ids = np.concatenate([ids, np.zeros(-ids.size % ROWS, np.int32)])
    n_blocks = ids.size // ROWS
    pos = np.arange(ids.size)
    # how far back each position's conv window reaches, and where the
    # state restarts: everywhere as published, unless a control says
    back = np.full(ids.size, 3)
    if not conv_carry:
        back = np.where(pos < prompt, np.minimum(pos % chunk, 3), 0)
    back = np.minimum(back, pos)
    reset = (pos % chunk == 0) & (pos > 0) & (pos < prompt) if reset_state \
        else np.zeros(ids.size, bool)
    back_b = [jnp.asarray(b, jnp.int32) for b in np.split(back, n_blocks)]
    reset_b = [jnp.asarray(r) for r in np.split(reset, n_blocks)]
    starts = [jnp.int32(i * ROWS) for i in range(n_blocks)]
    positions = jnp.asarray(np.asarray(positions), jnp.int32)
    kinds = layer_kinds(cfg)
    gap = None
    with jax.default_matmul_precision("highest"):
        h = [_embed(params["embed"], blk, compute=compute)
             for blk in np.split(ids, n_blocks)]
        for li, kind in enumerate(kinds):
            lp = params["layers"][li]
            x = _blocks(_norm, h, w=lp["n1"], eps=eps)
            if kind == "linear_attention":
                y = _gdn(x, lp, cfg, back=back_b, reset=reset_b, decay=decay)
            else:
                y = _attention(x, lp, cfg, starts)
            h = [a + b for a, b in zip(h, y)]
            x = _blocks(_norm, h, w=lp["n2"], eps=eps)
            y, g = _experts(x, lp, cfg, shared_gate=shared_gate)
            h = [a + b for a, b in zip(h, y)]
            at = g[positions]
            gap = at if gap is None else jnp.minimum(gap, at)
        # multi-token prediction is not computed: the main model's
        # logits do not depend on it
        top = jnp.concatenate(h)[positions]
        logits = _head(top, params["final_norm"], params["head"], eps=eps)
    return {"logits": np.asarray(logits, np.float32),
            "route_gap": np.asarray(gap, np.float32)}


def logit_error(served: np.ndarray, ref: np.ndarray) -> float:
    """Largest difference of two logit rows over the reference's spread
    (its standard deviation over the vocabulary)."""
    return float(np.max(np.abs(served - ref)) / (np.std(ref) + 1e-30))


def rank_gap(served_token: int, ref: np.ndarray) -> float:
    """How far the served token's logit lies below the reference's best.
    A greedy step that agrees reads 0."""
    return float(max(0.0, np.max(ref) - ref[served_token]))
