"""Plain reference of Xing4.0-29B-A4B (``xing4_0``): the full forward
pass of ONE sequence in float32, with no cache, no paging, no batching
and no kernel.

It holds the same bfloat16 weight values as the program, upcasts a
layer (and one expert) at a time, computes under
``jax.default_matmul_precision("highest")`` (a float32 product on the
TPU is otherwise one bfloat16 pass), and runs every stage that holds a
product on blocks of ``ROWS`` rows, attention on blocks of queries
against keys from a short list of lengths, so that a 37k-token sequence
fits and a handful of programs compile (``afmoe_ref.py`` says why). It
imports nothing of the program. Attention is the EXPANDED form only:
every position's latent through ``Wkvb`` into 32 heads of keys and
values; the absorbed form and the latent cache are the program's.

The residual state of a token is ``X`` (streams, hidden), the embedding
copied into every stream. Around each sublayer ``F`` (attention with
its norm, the dense MLP or the experts with theirs) the
manifold-constrained hyper-connection: ``z = vec(X) / sqrt(mean(vec(X)^2)
+ hc_eps)``; ``Hp = a0 (z P)[:n] + b[:n]``, ``Ho = a1 (z P)[n:2n] +
b[n:2n]``, ``Hr = a2 mat((z P)[2n:]) + mat(b[2n:])`` (row-major);
``H_pre = sigmoid(Hp)``, ``H_post = 2 sigmoid(Ho)``, ``H_res =
SK(clamp(Hr))`` where ``SK`` starts from ``exp`` and ``hc_sinkhorn_iters``
times divides each row by its sum plus ``hc_eps``, then each column;
``u = H_pre X``, ``X' = H_res X + H_post^T F(u)``. The streams are summed
before the final norm.

Attention, ``x = N1(u)``: ``c_q = RMSNorm(x Wqa)``, ``q = c_q Wqb`` as
heads of ``[nope | rope]``; ``[c_kv | k_r] = x Wkva``; ``c =
RMSNorm(c_kv)``; ``[k_nope_h | v_h] = c Wkvb[h]``; rotate-half YaRN
rotary on each head's ``q_rope`` and on the ONE ``k_r`` all heads share;
``s = (q_nope . k_nope + q_rope . k_rope) * scale`` over ``j <= t``,
``scale = (nope + rope)^-0.5 * (0.1 mscale_all_dim ln factor + 1)^2``;
``out = concat(softmax(s) v) Wo``. Experts as ``afmoe_ref.py``:
``p = sigmoid(x Wr)``, the top k of ``p + b``, weights ``p`` at the
chosen over their sum times ``routed_scaling_factor``, plus the shared
expert.

Departures from the published description, each at its line below:
multi-token prediction is not computed (the main model's logits do not
depend on it); what the config does not fix (the configuration file's
``assumed``) is marked ``(A)``.

``compute`` is the control's: the same pass with the residual streams,
norms, mappings, Sinkhorn, router, softmax and logits in another dtype
(every stage takes its dtype from the activations it is given, so only
the embedding names it). ``h_res="identity"``, ``sinkhorn_iters`` and
``rope_key=False`` are controls too: each leaves a part of the
mathematics out, and the comparison has to notice.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
ROWS = 256          # rows a block: queries of an attention block too
K_BUCKET = 8192
HEAD_GROUP = 8      # heads whose expanded keys and values are held at once


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(x.dtype)


def yarn_inv_freq(dim, theta, scaling) -> np.ndarray:
    """``dim / 2`` frequencies (``rope_scaling`` of type ``yarn``)."""
    factor = scaling["factor"]
    original = scaling["original_max_position_embeddings"]

    def turns_at(beta):
        return dim * math.log(original / (beta * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(turns_at(scaling["beta_fast"])), 0)
    high = min(math.ceil(turns_at(scaling["beta_slow"])), dim - 1)
    f = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    m = 1.0 - ramp
    return (f / factor * (1 - m) + f * m).astype(np.float32)


def softmax_scale(cfg) -> float:
    s = cfg["rope_scaling"]
    m = 0.1 * s["mscale_all_dim"] * math.log(s["factor"]) + 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def _rope(x, pos, inv):
    """(A) rotate-half pairing (dimension i with i + half) over the rope
    dims; mscale = mscale_all_dim, so cos and sin are unscaled."""
    hd = x.shape[-1]
    ang = pos.astype(F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _blocks(fn, *rows, **kw):
    """``fn`` over lists of row blocks; returns a list (or a tuple of
    lists) of its results."""
    outs = [fn(*args, **kw) for args in zip(*rows)]
    return list(zip(*outs)) if isinstance(outs[0], tuple) else outs


def sinkhorn(logits, iters: int, eps: float):
    """``exp(logits)`` (..., n, n): ``iters`` times rows over their sums,
    then columns over theirs."""
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (jnp.sum(m, -1, keepdims=True) + eps)
        m = m / (jnp.sum(m, -2, keepdims=True) + eps)
    return m


@partial(jax.jit, static_argnames=("how",))
def _hc_maps(xs, proj, bias, alpha, *, how):
    """One block's mappings from its residual state ``xs`` (rows, n, H):
    ``(u (rows, H), H_post (rows, n), H_res (rows, n, n), the largest
    distance of a row or column sum of any of the block's H_res from
    1)``."""
    iters, eps, lo, hi, identity = how
    rows, n, _h = xs.shape
    dt = xs.dtype
    flat = xs.reshape(rows, -1)
    # (A) the norm over vec(X) has no learned weight; hc_eps inside the
    # root, and again in Sinkhorn's divisions
    z = flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True) + eps)
    m = z @ proj.astype(dt)
    a, b = alpha.astype(dt), bias.astype(dt)
    pre = jax.nn.sigmoid(a[0] * m[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * m[:, n:2 * n] + b[n:2 * n])
    logits = (a[2] * m[:, 2 * n:] + b[2 * n:]).reshape(rows, n, n)
    res = sinkhorn(jnp.clip(logits, lo, hi), iters, eps)
    if identity:                        # a control: no mixing of streams
        res = jnp.broadcast_to(jnp.eye(n, dtype=dt), res.shape)
    # how far H_res is from doubly stochastic: its worst row or column
    defect = jnp.maximum(jnp.max(jnp.abs(jnp.sum(res, -1) - 1.0)),
                         jnp.max(jnp.abs(jnp.sum(res, -2) - 1.0)))
    return jnp.einsum("tn,tnh->th", pre, xs), post, res, defect


@jax.jit
def _hc_apply(xs, y, post, res):
    return jnp.einsum("tij,tjh->tih", res, xs) + post[:, :, None] \
        * y[:, None, :]


@partial(jax.jit, static_argnames=("dims",))
def _latents(u, p0, n1, wqa, qan, wkva, kvn, inv, *, dims):
    """One block of rows from position ``p0`` on: the query's latent
    ``c_q`` (rows, q rank), the key/value latent ``c`` (rows, kv rank)
    and the ONE rotated rope key all heads share (rows, rope)."""
    rank, eps, rope_key = dims
    pos = p0 + jnp.arange(u.shape[0])
    x = _rms(u, n1, eps)
    dt = x.dtype
    kva = x @ wkva.astype(dt)
    k_rope = _rope(kva[:, None, rank:], pos, inv)[:, 0]
    if not rope_key:                    # a control: positions unseen
        k_rope = jnp.zeros_like(k_rope)
    return (_rms(x @ wqa.astype(dt), qan, eps), _rms(kva[:, :rank], kvn, eps),
            k_rope)


@partial(jax.jit, static_argnames=("dims",))
def _expand(cq, c, k_rope, p0, wqb, wkvb, inv, *, dims):
    """A group of heads of one block, EXPANDED: ``wqb`` (q rank, heads,
    nope + rope) and ``wkvb`` (kv rank, heads, nope + v) hold the
    group's columns; q rotated; the rope key copied to every head."""
    nope, = dims
    dt = cq.dtype
    pos = p0 + jnp.arange(cq.shape[0])
    q = jnp.einsum("tr,rhd->thd", cq, wqb.astype(dt))
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, inv)], -1)
    full = jnp.einsum("tr,rhd->thd", c, wkvb.astype(dt))
    k = jnp.concatenate([full[..., :nope], jnp.broadcast_to(
        k_rope[:, None, :], full.shape[:2] + k_rope.shape[-1:])], -1)
    return q, k, full[..., nope:]


@partial(jax.jit, static_argnames=("klen",))
def _keys(k, v, *, klen):
    return k[:klen], v[:klen]


@partial(jax.jit, static_argnames=("scale",))
def _attend(qb, kb, vb, q0, *, scale):
    """One block of queries from ``q0`` against keys from 0 (keys after
    a query, and the padding, are masked)."""
    s = jnp.einsum("qhd,khd->hqk", qb, kb) * scale
    qp = q0 + jnp.arange(qb.shape[0])[:, None]
    kp = jnp.arange(kb.shape[0])[None, :]
    s = jnp.where((kp <= qp)[None], s, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), vb)


def _attention(q_blocks, k_blocks, v_blocks, scale):
    """Each block of ``ROWS`` queries against the keys up to the next
    multiple of ``K_BUCKET``."""
    t = len(q_blocks) * ROWS
    pad = -t % K_BUCKET
    k = jnp.concatenate(list(k_blocks) + [jnp.zeros(
        (pad,) + k_blocks[0].shape[1:], k_blocks[0].dtype)])
    v = jnp.concatenate(list(v_blocks) + [jnp.zeros(
        (pad,) + v_blocks[0].shape[1:], v_blocks[0].dtype)])
    outs = []
    for i, qb in enumerate(q_blocks):
        q1 = (i + 1) * ROWS
        kb, vb = _keys(k, v, klen=q1 + (-q1 % K_BUCKET))
        outs.append(_attend(qb, kb, vb, jnp.int32(i * ROWS), scale=scale))
    return outs


@jax.jit
def _out(wo, *groups):
    o = jnp.concatenate(groups, axis=1)             # heads in order
    return o.reshape(o.shape[0], -1) @ wo.astype(o.dtype)


@partial(jax.jit, static_argnames=("eps",))
def _norm(u, w, *, eps):
    return _rms(u, w, eps)


@jax.jit
def _swiglu(x, gate, up, down):
    dt = x.dtype
    return (jax.nn.silu(x @ gate.astype(dt)) * (x @ up.astype(dt))) \
        @ down.astype(dt)


@partial(jax.jit, static_argnames=("k", "norm", "scale"))
def _route(x, router, bias, *, k, norm, scale):
    s = jax.nn.sigmoid(x @ router.astype(x.dtype))
    top, chosen = jax.lax.top_k(s + bias.astype(x.dtype), k + 1)
    w = jnp.take_along_axis(s, chosen[:, :k], -1)
    if norm:
        w = w / jnp.sum(w, -1, keepdims=True)
    # the margin by which the choice stands: k-th over (k+1)-th
    return chosen[:, :k], w * scale, top[:, k - 1] - top[:, k]


@jax.jit
def _expert_rows(xg, wt, gate, up, down, e):
    """Expert ``e`` (a traced index into the stacked weights) on the
    gathered rows ``xg``, weighted by ``wt``."""
    pick = lambda w: jax.lax.dynamic_index_in_dim(w, e, keepdims=False)  # noqa: E731
    return _swiglu(xg, pick(gate), pick(up), pick(down)) \
        * wt.astype(xg.dtype)[:, None]


_gather = jax.jit(lambda x, idx: x[idx])
_scatter_add = jax.jit(lambda y, idx, ye: y.at[idx].add(ye))


def _bucket(n: int) -> int:
    b = 256
    while b < n:
        b *= 4
    return b


def _experts(x_blocks, lp, cfg):
    """Returns (y blocks, gaps): gaps (T,) the margin by which each
    token's top-k choice stands."""
    chosen, w, gaps = _blocks(
        _route, x_blocks, router=lp["router"], bias=lp["bias"],
        k=cfg["num_experts_per_tok"], norm=bool(cfg["norm_topk_prob"]),
        scale=float(cfg["routed_scaling_factor"]))
    chosen_h = np.concatenate([np.asarray(c) for c in chosen])
    w_h = np.concatenate([np.asarray(a, np.float32) for a in w])
    x = jnp.concatenate(x_blocks)
    y = jnp.zeros_like(x)
    for e in range(cfg["n_routed_experts"]):
        rows, slots = np.nonzero(chosen_h == e)
        if not rows.size:
            continue
        pad = _bucket(rows.size) - rows.size
        idx = np.concatenate([rows, np.zeros(pad, rows.dtype)]).astype(
            np.int32)
        wt = np.concatenate([w_h[rows, slots], np.zeros(pad, np.float32)])
        ye = _expert_rows(_gather(x, idx), wt, lp["e_gate"], lp["e_up"],
                          lp["e_down"], np.int32(e))
        y = _scatter_add(y, idx, ye)
    shared = _blocks(_swiglu, x_blocks, gate=lp["s_gate"], up=lp["s_up"],
                     down=lp["s_down"])
    return [a + b for a, b in zip(jnp.split(y, len(x_blocks)), shared)], \
        jnp.concatenate(gaps)


@partial(jax.jit, static_argnames=("streams", "compute"))
def _embed(table, ids, *, streams, compute):
    # (A) the input: the embedding copied into every stream
    return jnp.repeat(table[ids].astype(compute)[:, None, :], streams, 1)


@partial(jax.jit, static_argnames=("eps",))
def _head(top, norm, head, *, eps):
    # (A) the readout: the streams summed, then the final norm
    return _rms(jnp.sum(top, axis=1), norm, eps) @ head.astype(top.dtype)


def forward(params: dict, cfg: dict, ids, positions, *, compute=F32,
            h_res: str = "sinkhorn", sinkhorn_iters: int | None = None,
            rope_key: bool = True) -> dict:
    """The whole sequence ``ids`` (T,) through the ``num_hidden_layers``
    layers of ``cfg`` (the configuration file's dict, HF keys). Returns
    ``logits`` (len(positions), V) float32 at the asked positions and
    ``route_gap`` (len(positions),): the smallest margin, over the expert
    layers, by which a position's top-k choice stands, and ``hc_defect``,
    the largest distance of a row or column sum of any ``H_res`` of the
    pass from 1. The keywords are the controls' (module docstring)."""
    if h_res not in ("sinkhorn", "identity"):
        raise ValueError(f"h_res {h_res!r}: sinkhorn or identity")
    eps = float(cfg["rms_norm_eps"])
    n = int(cfg["hc_mult"])
    how = (int(cfg["hc_sinkhorn_iters"] if sinkhorn_iters is None
               else sinkhorn_iters), float(cfg["hc_eps"]),
           float(cfg["mhc_h_res_clamp_min"]),
           float(cfg["mhc_h_res_clamp_max"]), h_res == "identity")
    nh, rank, nope = (cfg["num_attention_heads"], cfg["kv_lora_rank"],
                      cfg["qk_nope_head_dim"])
    group = min(HEAD_GROUP, nh)
    inv = jnp.asarray(yarn_inv_freq(cfg["qk_rope_head_dim"],
                                    float(cfg["rope_theta"]),
                                    cfg["rope_scaling"]))
    scale = softmax_scale(cfg)
    ids = np.asarray(ids, np.int32)
    ids = np.concatenate([ids, np.zeros(-ids.size % ROWS, np.int32)])
    positions = jnp.asarray(np.asarray(positions), jnp.int32)
    n_blocks = ids.size // ROWS
    starts = [jnp.int32(i * ROWS) for i in range(n_blocks)]
    defects: list = []

    def sublayer(xs, lp, key, fn):
        u, post, res, defect = _blocks(
            _hc_maps, xs, proj=lp[f"{key}_w"], bias=lp[f"{key}_b"],
            alpha=lp[f"{key}_a"], how=how)
        defects.extend(defect)
        y = fn(list(u))
        for i in range(len(xs)):        # in place: one state, not two
            xs[i] = _hc_apply(xs[i], y[i], post[i], res[i])
        return xs

    with jax.default_matmul_precision("highest"):
        xs = [_embed(params["embed"], blk, streams=n, compute=compute)
              for blk in np.split(ids, n_blocks)]
        gap = None
        for li in range(cfg["num_hidden_layers"]):
            lp = params["layers"][li]

            def attention(u, lp=lp):
                cq, c, k_rope = _blocks(
                    _latents, u, starts, n1=lp["n1"], wqa=lp["wqa"],
                    qan=lp["qan"], wkva=lp["wkva"], kvn=lp["kvn"], inv=inv,
                    dims=(rank, eps, bool(rope_key)))
                wqb = lp["wqb"].reshape(lp["wqb"].shape[0], nh, -1)
                wkvb = lp["wkvb"].reshape(rank, nh, -1)
                heads = []      # a group of heads at a time: 37k positions
                for g in range(0, nh, group):   # of 32 heads do not fit
                    q, k, v = _blocks(
                        _expand, cq, c, k_rope, starts,
                        wqb=wqb[:, g:g + group], wkvb=wkvb[:, g:g + group],
                        inv=inv, dims=(nope,))
                    heads.append(_attention(q, k, v, scale))
                return [_out(lp["wo"], *blk) for blk in zip(*heads)]

            gaps = []

            def mlp(u, lp=lp, li=li, gaps=gaps):
                x = _blocks(_norm, u, w=lp["n2"], eps=eps)
                if li < cfg["first_k_dense_replace"]:
                    return _blocks(_swiglu, x, gate=lp["w_gate"],
                                   up=lp["w_up"], down=lp["w_down"])
                y, g = _experts(x, lp, cfg)
                gaps.append(g)
                return y

            xs = sublayer(xs, lp, "hca", attention)
            xs = sublayer(xs, lp, "hcm", mlp)
            if gaps:
                at = gaps[0][positions]
                gap = at if gap is None else jnp.minimum(gap, at)
        # multi-token prediction (num_nextn_predict_layers) is not
        # computed: the main model's logits do not depend on it
        top = jnp.concatenate(xs)[positions]
        logits = _head(top, params["final_norm"], params["head"], eps=eps)
    return {"logits": np.asarray(logits, np.float32),
            "route_gap": np.asarray(gap, np.float32) if gap is not None
            else np.full(len(positions), np.inf),
            "hc_defect": float(max(np.asarray(jnp.stack(defects),
                                              np.float32)))}


def logit_error(served: np.ndarray, ref: np.ndarray) -> float:
    """Largest difference of two logit rows over the reference's spread
    (its standard deviation over the vocabulary)."""
    return float(np.max(np.abs(served - ref)) / (np.std(ref) + 1e-30))


def rank_gap(served_token: int, ref: np.ndarray) -> float:
    """How far the served token's logit lies below the reference's best.
    A greedy step that agrees reads 0."""
    return float(max(0.0, np.max(ref) - ref[served_token]))
