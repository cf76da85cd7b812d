"""The expert layer: sigmoid routing with a selection bias or softmax
routing without one, top-k of all experts, dropless grouped products, a
shared expert beside them where the model has one.

``route`` is float32 end to end (scores, the biased choice, the
normalised weights). ``experts`` sorts the (token, choice) pairs by
expert and runs ONE grouped product per projection over every expert
(``jax.lax.ragged_dot``: rows of the sorted activations against the
expert their group names), so no token is dropped and no capacity is
set; a token's ``k`` results are weighted and summed back in token
order (:func:`combine`). bfloat16 operands, float32 accumulation. A
layer that holds a share of the experts (one chip's of an
expert-parallel deployment) routes over all of them and computes the
part its own give.

The combine takes one of two forms, chosen by :func:`combine_form` from
the backend and the shapes (no knob): on a TPU one Mosaic kernel that
fetches each token's ``k`` rows by position and weights and sums them in
VMEM (``moe_kernel.py``); elsewhere XLA weights every row, gathers them
back into token order and sums. Both find a pair's row by the inverse of
the dispatch's sort, made by a scatter, not a second sort.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from vlog_tpu.lm import moe_kernel

F32 = jnp.float32
BF16 = jnp.bfloat16


def route(x: jax.Array, router: jax.Array, bias: jax.Array | None, *,
          top_k: int, route_norm: bool, route_scale: float,
          score_func: str = "sigmoid"
          ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """``x`` (T, H) float32, ``router`` (H, E), ``bias`` (E,) float32 or
    ``None``. Returns ``(chosen (T, k) int32, weights (T, k) float32,
    scores (T, E))``: the scores are ``sigmoid`` or ``softmax`` (over the
    experts) of the router's logits, the choice is by ``score + bias``,
    the weight is the score alone, over the chosen's sum where
    ``route_norm``, times ``route_scale``."""
    with jax.named_scope("lm.moe.router"):
        logits = jnp.dot(
            x.astype(F32), router.astype(F32), precision=lax.Precision.HIGHEST,
            preferred_element_type=F32)
        scores = jax.nn.softmax(logits, axis=-1) if score_func == "softmax" \
            else jax.nn.sigmoid(logits)
        _, chosen = lax.top_k(
            scores if bias is None else scores + bias.astype(F32), top_k)
        weights = jnp.take_along_axis(scores, chosen, axis=-1)
        if route_norm:
            weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                                 + 1e-20)
        return chosen.astype(jnp.int32), weights * route_scale, scores


def swiglu(x: jax.Array, gate: jax.Array, up: jax.Array, down: jax.Array
           ) -> jax.Array:
    """``down(silu(gate x) * (up x))`` on (T, H) rows; float32 out."""
    xb = x.astype(BF16)
    g = jnp.dot(xb, gate, preferred_element_type=F32)
    u = jnp.dot(xb, up, preferred_element_type=F32)
    return jnp.dot((jax.nn.silu(g) * u).astype(BF16), down,
                   preferred_element_type=F32)


def experts(x: jax.Array, chosen: jax.Array, weights: jax.Array,
            gate: jax.Array, up: jax.Array, down: jax.Array,
            valid: jax.Array, held: tuple[int, int] | None = None
            ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The routed part. ``x`` (T, H), ``chosen``/``weights`` (T, k),
    ``gate``/``up`` (E, H, I), ``down`` (E, I, H), ``valid`` (T,) bool.
    Returns ``(out (T, H) float32, tokens per expert (E,) int32, experts
    that hold any row () int32)``; the count is of valid tokens only
    (padding is computed, not counted), the experts held are of every
    row (their weights are read).

    ``held`` ``(first, end)``: the router outputs whose experts the
    stacks hold (``E`` of them; default all, ``chosen`` then indexes the
    stacks). A pair routed outside them is sorted past every group, so
    the grouped products neither compute it nor weight it: the part of
    the result that this share of the experts gives."""
    t, k = chosen.shape
    n_experts = gate.shape[0]
    with jax.named_scope("lm.moe.dispatch"):
        flat = chosen.reshape(-1)
        if held is not None:
            local = flat - held[0]
            mine = (local >= 0) & (local < n_experts)
            # past the last group: no size counts it, bincount drops it
            flat = jnp.where(mine, local, n_experts)
            weights = jnp.where(mine.reshape(t, k), weights, 0.0)
        order = jnp.argsort(flat, stable=True)
        sizes = jnp.bincount(flat, length=n_experts).astype(jnp.int32)
        counted = jnp.bincount(
            flat, weights=jnp.repeat(valid, k).astype(jnp.int32),
            length=n_experts).astype(jnp.int32)
        xs = x.astype(BF16)[order // k]
    with jax.named_scope("lm.moe.experts"):
        g = lax.ragged_dot(xs, gate, sizes, preferred_element_type=F32)
        u = lax.ragged_dot(xs, up, sizes, preferred_element_type=F32)
        ys = lax.ragged_dot((jax.nn.silu(g) * u).astype(BF16), down, sizes,
                            preferred_element_type=F32)
    out = combine(ys, order, weights,
                  None if held is None else mine.reshape(t, k),
                  form=combine_form(t, k, ys.shape[-1]))
    return out, counted, jnp.sum(sizes > 0).astype(jnp.int32)


def combine_form(t: int, k: int, h: int) -> str:
    """``"kernel"`` or ``"xla"``: how the ``k`` rows of each of ``t``
    tokens, ``h`` wide, are summed back (:func:`combine`): the kernel on a
    TPU where Mosaic tiles the shapes, XLA elsewhere (shapes and backend,
    as ``model.py::attention_form``; no knob)."""
    kernel = moe_kernel.supported(t, k, h)
    return "kernel" if kernel and jax.default_backend() == "tpu" else "xla"


# one trace and one lowered callee a shape: every expert layer of a
# program calls the same
@functools.partial(jax.jit, static_argnames=("form", "interpret"))
def combine(ys: jax.Array, order: jax.Array, weights: jax.Array,
            mine: jax.Array | None, *, form: str,
            interpret: bool = False) -> jax.Array:
    """``ys`` (T*k, H) float32, the pairs' rows in the dispatch's
    ``order``; ``weights`` (T, k) float32 in token order (0 for a pair
    off this chip's experts); ``mine`` (T, k) bool, the pairs this chip's
    experts computed (``None``: every pair). Returns (T, H) float32, each
    token's rows times their weights, summed: by the kernel in the order
    ``j = 0 .. k-1``, as XLA sums them on a CPU (bit for bit); XLA's TPU
    reduce adds the ``k`` terms in another order (the last bits)."""
    t, k = weights.shape
    n = t * k
    with jax.named_scope("lm.moe.combine"):
        # each pair's row in expert order: the inverse of ``order``
        back = jnp.zeros((n,), jnp.int32).at[order].set(
            jnp.arange(n, dtype=jnp.int32), unique_indices=True)
        if form == "kernel":
            return moe_kernel.combine(ys, back.reshape(t, k), weights, mine,
                                      interpret=interpret)
        ys = ys * weights.reshape(-1)[order][:, None]
        return ys[back].reshape(t, k, -1).sum(axis=1)
