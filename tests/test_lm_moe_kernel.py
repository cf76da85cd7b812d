"""The experts' combine: the Mosaic kernel (Pallas's interpreter in the
chip's place) against the XLA form, through ``moe.experts`` as the
model calls it, and the scatter-built inverse against the second sort
it replaced."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vlog_tpu.lm import moe, moe_kernel

F32 = jnp.float32
BF16 = jnp.bfloat16


def _layer(t, k, h, n_experts, *, routed=None, seed=0, dupes=False):
    """Seeded inputs of ``moe.experts``: ``(x, chosen, weights, gate,
    up, down, valid)``; ``routed`` router outputs (default the experts
    held); ``dupes``: several of a token's pairs on one expert."""
    rng = np.random.default_rng(seed)
    routed = routed or n_experts
    x = jnp.asarray(rng.standard_normal((t, h)), F32)
    if dupes:
        chosen = rng.integers(0, 3, (t, k))
    else:
        chosen = np.stack([rng.permutation(routed)[:k] for _ in range(t)])
    weights = jnp.asarray(rng.random((t, k)), F32)
    w = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.2, BF16)  # noqa: E731
    valid = jnp.asarray(rng.random(t) < 0.8)
    return (x, jnp.asarray(chosen, jnp.int32), weights, w(n_experts, h, 32),
            w(n_experts, h, 32), w(n_experts, 32, h), valid)


def _both_forms(monkeypatch, args, held=None):
    """``moe.experts``'s result in the XLA form, then with the combine
    steered to the kernel."""
    want = moe.experts(*args, held=held)
    monkeypatch.setattr(moe, "combine_form", lambda t, k, h: "kernel")
    monkeypatch.setattr(moe, "combine", functools.partial(
        moe.combine, interpret=True))
    return want, moe.experts(*args, held=held)


@pytest.mark.parametrize("case", [
    dict(t=40, k=4, h=256, n_experts=16),                  # k 4
    dict(t=64, k=8, h=128, n_experts=16),                  # k 8
    dict(t=48, k=10, h=256, n_experts=12, routed=24),      # half elsewhere
    dict(t=32, k=8, h=128, n_experts=8, dupes=True),       # one expert, twice
    dict(t=200, k=4, h=128, n_experts=8),                  # a ragged last tile
])
def test_the_kernel_equals_the_xla_combine(monkeypatch, case):
    """Bit for bit: the same float32 products summed from 0.0 in the
    order ``j = 0 .. k-1``; padded tokens (``valid`` false) are combined
    as any other; a pair routed to another chip's experts (``held`` a
    share of the router's outputs) is neither fetched nor added."""
    routed = case.get("routed")
    held = (0, case["n_experts"]) if routed else None
    args = _layer(case["t"], case["k"], case["h"], case["n_experts"],
                  routed=routed, dupes=case.get("dupes", False))
    want, got = _both_forms(monkeypatch, args, held)
    assert not bool(args[-1].all())
    if held:
        off = args[1] >= case["n_experts"]
        assert 0.3 < float(off.mean()) < 0.7
    if case["t"] > moe_kernel.TILE:
        assert case["t"] % moe_kernel.tile_for(
            case["t"], case["k"], case["h"])
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    for a, b in zip(got[1:], want[1:]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_kernel_skips_the_rows_of_pairs_it_does_not_hold():
    """A pair that is not ``mine`` is neither fetched nor added: its
    row, filled with NaN, leaves the sums as they were."""
    rng = np.random.default_rng(3)
    t, k, h = 24, 4, 128
    ys = rng.standard_normal((t * k, h)).astype(np.float32)
    pos = rng.permutation(t * k).reshape(t, k).astype(np.int32)
    weights = rng.random((t, k)).astype(np.float32)
    off = rng.random((t, k)) < 0.5
    weights[off] = 0.0

    def summed(rows):
        return np.asarray(moe_kernel.combine(
            jnp.asarray(rows), jnp.asarray(pos), jnp.asarray(weights),
            jnp.asarray(~off), interpret=True))

    poisoned = ys.copy()
    poisoned[pos[off]] = np.nan
    want = summed(ys)
    assert np.isfinite(want).all()
    np.testing.assert_array_equal(summed(poisoned), want)


def test_the_scatter_inverse_equals_the_second_sort():
    """The XLA form finds a pair's row by a scatter of ``arange`` into
    the dispatch's order; it sums exactly what ``argsort(order)`` did."""
    t, k, h = 96, 8, 128
    x, chosen, weights, *_ = _layer(t, k, h, 16, seed=4)
    order = jnp.argsort(chosen.reshape(-1), stable=True)
    ys = jnp.asarray(np.random.default_rng(5).standard_normal((t * k, h)),
                     F32)

    @jax.jit
    def sorted_twice(ys, order, weights):
        ys = ys * weights.reshape(-1)[order][:, None]
        back = jnp.argsort(order)
        return ys[back].reshape(t, k, -1).sum(axis=1)

    np.testing.assert_array_equal(
        np.asarray(moe.combine(ys, order, weights, None, form="xla")),
        np.asarray(sorted_twice(ys, order, weights)))


def test_the_combine_form_is_read_from_the_call(monkeypatch):
    """The kernel on a TPU where Mosaic tiles the shapes (every bucket
    of the four transcript cells: tokens the chunk plus the rows), XLA on
    any other backend and for shapes it does not tile; no knob."""
    form = moe.combine_form
    assert form(2080, 8, 2048) == "xla"                        # the CPU
    monkeypatch.setattr(moe.jax, "default_backend", lambda: "tpu")
    cells = {(32, 8, 2048): 2048, (16, 8, 2048): 2048,         # Trinity, Keye
             (32, 4, 3584): 2048, (64, 10, 2048): 2048}        # Xing, Qwen
    for (rows, k, h), chunk in cells.items():
        for c in (0, 256, 512, 1024, chunk):
            assert form(c + rows, k, h) == "kernel", (rows, k, h, c)
    assert form(12, 2, 64) == "xla"         # the tests' tiny models
    assert form(2080, 8, 2000) == "xla"     # not whole lane blocks
    assert form(2084, 8, 2048) == "xla"     # not whole sublane tiles
    assert form(2080, 64, 8192) == "xla"    # two slots past the buffer
