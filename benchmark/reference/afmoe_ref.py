"""Plain reference of the ``afmoe`` decoder (Trinity-Mini's published
form): the full forward pass of ONE sequence in float32, with no cache,
no paging, no batching and no kernel.

It holds the same bfloat16 weight values as the program, upcasts a
layer (and one expert) at a time, computes under
``jax.default_matmul_precision("highest")`` (a float32 product on the
TPU is otherwise one bfloat16 pass), and runs attention in blocks of
queries so that a 36k-token sequence fits. It imports nothing of the
program. What the published config does not fix is a keyword of
:func:`forward` (``MECHANISMS``), so that a test can leave each out and
see the comparison fail. ``compute`` is the control's: the same pass
with the residual stream, norms, router, softmax and logits in another
dtype (every stage takes its dtype from the activations it is given, so
only the embedding names it).

Layer: ``h = h + N2(Attn(N1(h)))``, ``h = h + N4(Mlp(N3(h)))``.
Attention: 32 query heads over 4 K/V heads (head ``i`` reads K/V head
``i // 8``), RMSNorm over each head of q and k, rotate-half rotary on
``sliding_attention`` layers only, keys ``j <= p`` on a full layer and
``p - window < j <= p`` on a window layer, output
``(heads * sigmoid(x Wg)) Wo``. Experts: ``s = sigmoid(x Wr)``, top k of
``s + b``, weights ``s`` at the chosen over their sum times
``route_scale``, plus the shared expert.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
SLIDING = "sliding_attention"
# what the config's keys do not fix (ISSUE 29, marked (A)); each can be
# switched off to show that the comparison notices
MECHANISMS = ("window_mask", "nope_on_full", "output_gate", "head_norms",
              "selection_bias", "route_norm", "route_scale", "shared_expert",
              "embedding_scale", "post_norms")
ROWS = 256          # rows a block: queries of an attention block too
K_BUCKET = 8192


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(x.dtype)


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos.astype(F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


# The TPU's compiler takes 3 to 5 s for every float32 ``highest`` product
# (12 s for a stage with four; my chip run and the sandbox's v5e compiles,
# PR 29), and a first version that ran whole sequences compiled every
# stage anew for every sequence length: 80 s for 700 tokens, never done
# for 36k. So every stage that holds a product runs on BLOCKS of
# ``ROWS`` rows (the sequence padded with zero rows, which stay zero), on
# ``klen`` keys from a short list, or on a few bucket sizes of an
# expert's rows: each compiles once, whatever the sequence's length.
# What depends on the length (slicing, gathering, concatenating) holds no
# product and compiles in a fraction of a second.

def _blocks(fn, *rows, **kw):
    """``fn`` over lists of row blocks; returns a list (or a tuple of
    lists) of its results."""
    outs = [fn(*args, **kw) for args in zip(*rows)]
    return list(zip(*outs)) if isinstance(outs[0], tuple) else outs


@partial(jax.jit, static_argnames=("dims", "head_norms"))
def _qkv(h, n1, wq, wk, wv, qn, kn, *, dims, head_norms):
    nh, nkv, hd, eps = dims
    x = _rms(h, n1, eps)
    q = (x @ wq.astype(x.dtype)).reshape(-1, nh, hd)
    k = (x @ wk.astype(x.dtype)).reshape(-1, nkv, hd)
    v = (x @ wv.astype(x.dtype)).reshape(-1, nkv, hd)
    if head_norms:
        q, k = _rms(q, qn, eps), _rms(k, kn, eps)
    return x, q, k, v


@partial(jax.jit, static_argnames=("theta",))
def _rotary(q, k, p0, *, theta):
    pos = p0 + jnp.arange(q.shape[0])
    return _rope(q, pos, theta), _rope(k, pos, theta)


@partial(jax.jit, static_argnames=("klen",))
def _keys(k, v, k0, *, klen):
    return (jax.lax.dynamic_slice_in_dim(k, k0, klen),
            jax.lax.dynamic_slice_in_dim(v, k0, klen))


@partial(jax.jit, static_argnames=("window",))
def _attend(qb, kb, vb, q0, k0, *, window):
    """One block of queries from ``q0`` against keys from ``k0`` (keys
    after a query, and the padding, are masked)."""
    rep = qb.shape[1] // kb.shape[1]
    kb = jnp.repeat(kb, rep, axis=1)
    vb = jnp.repeat(vb, rep, axis=1)
    s = jnp.einsum("qhd,khd->hqk", qb, kb) / math.sqrt(qb.shape[-1])
    qp = q0 + jnp.arange(qb.shape[0])[:, None]
    kp = k0 + jnp.arange(kb.shape[0])[None, :]
    ok = kp <= qp
    if window is not None:
        ok &= kp > qp - window
    s = jnp.where(ok[None], s, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), vb)


def _attention(q_blocks, k_blocks, v_blocks, window):
    """Each block of ``ROWS`` queries against the keys it can see: on a
    full layer the keys up to the next multiple of ``K_BUCKET``, on a
    window layer the band's ``window + 2 ROWS`` keys."""
    t = len(q_blocks) * ROWS
    band = None if window is None else window + 2 * ROWS
    pad = -t % K_BUCKET + (band or 0)
    k = jnp.concatenate(list(k_blocks) + [jnp.zeros(
        (pad,) + k_blocks[0].shape[1:], k_blocks[0].dtype)])
    v = jnp.concatenate(list(v_blocks) + [jnp.zeros(
        (pad,) + v_blocks[0].shape[1:], v_blocks[0].dtype)])
    outs = []
    for i, qb in enumerate(q_blocks):
        q0, q1 = i * ROWS, (i + 1) * ROWS
        if window is None:
            k0, klen = 0, q1 + (-q1 % K_BUCKET)
        else:
            k0 = max(0, q0 - window + 1)
            k0, klen = k0 - k0 % ROWS, band
        kb, vb = _keys(k, v, jnp.int32(k0), klen=klen)
        outs.append(_attend(qb, kb, vb, jnp.int32(q0), jnp.int32(k0),
                            window=window))
    return outs


@partial(jax.jit, static_argnames=("eps", "gate", "post_norms"))
def _after_attention(h, x, o, wg, wo, n2, n3, *, eps, gate, post_norms):
    o = o.reshape(h.shape[0], -1)
    if gate:
        o = o * jax.nn.sigmoid(x @ wg.astype(x.dtype))
    o = o @ wo.astype(x.dtype)
    h = h + (_rms(o, n2, eps) if post_norms else o)
    return h, _rms(h, n3, eps)


@jax.jit
def _swiglu(x, gate, up, down):
    dt = x.dtype
    return (jax.nn.silu(x @ gate.astype(dt)) * (x @ up.astype(dt))) \
        @ down.astype(dt)


@partial(jax.jit, static_argnames=("eps", "post_norms"))
def _add_mlp(h, y, n4, *, eps, post_norms):
    return h + (_rms(y, n4, eps) if post_norms else y)


@partial(jax.jit, static_argnames=("k", "use_bias", "norm", "scale"))
def _route(x, router, bias, *, k, use_bias, norm, scale):
    s = jax.nn.sigmoid(x @ router.astype(x.dtype))
    biased = s + bias.astype(x.dtype) if use_bias else s
    top, chosen = jax.lax.top_k(biased, k + 1)
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


def _experts(x_blocks, lp, cfg, on):
    """Returns (y blocks, gaps): gaps (T,) the margin by which each
    token's top-k choice stands."""
    chosen, w, gaps = _blocks(
        _route, x_blocks, router=lp["router"], bias=lp["bias"],
        k=cfg["num_experts_per_tok"], use_bias=on["selection_bias"],
        norm=bool(on["route_norm"] and cfg["route_norm"]),
        scale=float(cfg["route_scale"]) if on["route_scale"] else 1.0)
    chosen_h = np.concatenate([np.asarray(c) for c in chosen])
    w_h = np.concatenate([np.asarray(a) for a in w])
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
                          lp["e_down"], np.int32(e))
        y = _scatter_add(y, idx, ye)
    y_blocks = list(jnp.split(y, len(x_blocks)))
    if on["shared_expert"] and cfg["num_shared_experts"]:
        shared = _blocks(_swiglu, x_blocks, gate=lp["s_gate"],
                         up=lp["s_up"], down=lp["s_down"])
        y_blocks = [a + b for a, b in zip(y_blocks, shared)]
    return y_blocks, jnp.concatenate(gaps)


@partial(jax.jit, static_argnames=("scale", "compute"))
def _embed(table, ids, *, scale, compute):
    return table[ids].astype(compute) * scale


@partial(jax.jit, static_argnames=("eps",))
def _head(top, norm, head, *, eps):
    return _rms(top, norm, eps) @ head.astype(top.dtype)


def forward(params: dict, cfg: dict, ids, positions, *,
            off: tuple[str, ...] = (), compute=F32) -> dict:
    """The whole sequence ``ids`` (T,) through the ``num_hidden_layers``
    layers of ``cfg`` (the configuration file's dict, HF keys). Returns
    ``logits`` (len(positions), V) float32 at the asked positions and
    ``route_gap`` (len(positions),): the smallest margin, over the expert
    layers, by which a position's top-k choice stands. ``off`` names
    mechanisms to leave out and ``compute`` another dtype for everything
    the configuration states as float32 (tests and controls only)."""
    on = {m: m not in off for m in MECHANISMS}
    unknown = set(off) - set(MECHANISMS)
    if unknown:
        raise ValueError(f"unknown mechanisms {sorted(unknown)}")
    eps = float(cfg["rms_norm_eps"])
    theta = float(cfg["rope_theta"])
    dims = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], eps)
    ids = np.asarray(ids, np.int32)
    ids = np.concatenate([ids, np.zeros(-ids.size % ROWS, np.int32)])
    positions = jnp.asarray(np.asarray(positions), jnp.int32)
    n_blocks = ids.size // ROWS
    with jax.default_matmul_precision("highest"):
        scale = math.sqrt(cfg["hidden_size"]) \
            if on["embedding_scale"] and cfg.get("mup_enabled") else 1.0
        h = [_embed(params["embed"], blk, scale=scale, compute=compute)
             for blk in np.split(ids, n_blocks)]
        gap = None
        for li in range(cfg["num_hidden_layers"]):
            lp = params["layers"][li]
            sliding = cfg["layer_types"][li] == SLIDING
            x, q, k, v = _blocks(
                _qkv, h, n1=lp["n1"], wq=lp["wq"], wk=lp["wk"], wv=lp["wv"],
                qn=lp["qn"], kn=lp["kn"], dims=dims,
                head_norms=on["head_norms"])
            if sliding or not on["nope_on_full"]:
                q, k = _blocks(
                    lambda a, b, i: _rotary(a, b, jnp.int32(i * ROWS),
                                            theta=theta),
                    q, k, range(n_blocks))
            window = cfg["sliding_window"] \
                if sliding and on["window_mask"] else None
            o = _attention(q, k, v, window)
            h, x = _blocks(_after_attention, h, x, o, wg=lp["wg"],
                           wo=lp["wo"], n2=lp["n2"], n3=lp["n3"], eps=eps,
                           gate=on["output_gate"],
                           post_norms=on["post_norms"])
            if li < cfg["num_dense_layers"]:
                y = _blocks(_swiglu, x, gate=lp["w_gate"], up=lp["w_up"],
                            down=lp["w_down"])
            else:
                y, gaps = _experts(x, lp, cfg, on)
                at = gaps[positions]
                gap = at if gap is None else jnp.minimum(gap, at)
            h = _blocks(_add_mlp, h, y, n4=lp["n4"], eps=eps,
                        post_norms=on["post_norms"])
        top = jnp.concatenate(h)[positions]
        logits = _head(top, params["final_norm"], params["head"], eps=eps)
    return {"logits": np.asarray(logits, np.float32),
            "route_gap": np.asarray(gap, np.float32) if gap is not None
            else np.full(len(positions), np.inf)}


def logit_error(served: np.ndarray, ref: np.ndarray) -> float:
    """Largest difference of two logit rows over the reference's spread
    (its standard deviation over the vocabulary)."""
    return float(np.max(np.abs(served - ref)) / (np.std(ref) + 1e-30))


def rank_gap(served_token: int, ref: np.ndarray) -> float:
    """``whisper_ref.rank_gap`` with k = 1 and every token allowed: how
    far the served token's logit lies below the reference's best. A
    greedy step that agrees reads 0."""
    return float(max(0.0, np.max(ref) - ref[served_token]))
