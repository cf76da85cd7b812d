"""Seeded Whisper weights, made on the device in one jitted call.

The benchmark owns the weights: the driver hands them to the program as
its ``params`` dict (HF names, torch layouts, what ``asr/model.py``
indexes), and the plain reference calls this module again with the same
seed once the program's state is freed. Nothing here imports the
program.

Every matrix, bias and embedding is N(0, 0.02^2) in float32; layer
norms are (1, 0). That is ``asr/model.py::random_state_dict``'s recipe
(copied, not imported; listed under Open questions in PERF.md), drawn
with ``jax.random`` on the device instead of NumPy on the host: 1 GB
(small) to 3 GB (medium) never cross PCIe and set-up pays milliseconds.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

INIT_STD = 0.02


def leaf_shapes(cfg: dict) -> list[tuple[str, tuple[int, ...], str]]:
    """``(hf_name, shape, kind)`` for every leaf, in a fixed order.

    ``kind`` is ``normal`` (drawn), ``ones`` or ``zeros`` (layer norm).
    ``cfg`` is the configuration file's dict (HF key names).
    """
    d = cfg["d_model"]
    out: list[tuple[str, tuple[int, ...], str]] = []

    def w(name, *shape):
        out.append((name, tuple(shape), "normal"))

    def ln(name):
        out.append((f"{name}.weight", (d,), "ones"))
        out.append((f"{name}.bias", (d,), "zeros"))

    w("model.encoder.conv1.weight", d, cfg["num_mel_bins"], 3)
    w("model.encoder.conv1.bias", d)
    w("model.encoder.conv2.weight", d, d, 3)
    w("model.encoder.conv2.bias", d)
    w("model.encoder.embed_positions.weight", cfg["max_source_positions"], d)
    w("model.decoder.embed_tokens.weight", cfg["vocab_size"], d)
    w("model.decoder.embed_positions.weight", cfg["max_target_positions"], d)
    ln("model.encoder.layer_norm")
    ln("model.decoder.layer_norm")
    for side, n_layers, ffn in (
            ("encoder", cfg["encoder_layers"], cfg["encoder_ffn_dim"]),
            ("decoder", cfg["decoder_layers"], cfg["decoder_ffn_dim"])):
        attns = ["self_attn"] if side == "encoder" else [
            "self_attn", "encoder_attn"]
        for i in range(n_layers):
            n = f"model.{side}.layers.{i}"
            for a in attns:
                w(f"{n}.{a}.q_proj.weight", d, d)
                w(f"{n}.{a}.q_proj.bias", d)
                w(f"{n}.{a}.k_proj.weight", d, d)      # no k bias in Whisper
                w(f"{n}.{a}.v_proj.weight", d, d)
                w(f"{n}.{a}.v_proj.bias", d)
                w(f"{n}.{a}.out_proj.weight", d, d)
                w(f"{n}.{a}.out_proj.bias", d)
                ln(f"{n}.{a}_layer_norm")
            w(f"{n}.fc1.weight", ffn, d)
            w(f"{n}.fc1.bias", ffn)
            w(f"{n}.fc2.weight", d, ffn)
            w(f"{n}.fc2.bias", d)
            ln(f"{n}.final_layer_norm")
    return out


def param_count(cfg: dict) -> int:
    return sum(int(np.prod(s)) for _, s, _ in leaf_shapes(cfg))


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """An ``rbg`` key from any non-negative whole number (the driver's
    seeds pass 2**31) and a stream number. ``rbg`` draws with the
    device's own bit generator: one cheap operation per draw to compile,
    where threefry unrolled over some 300 leaves took the chip's
    compiler 102 s (my chip run, PR 25)."""
    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(
        4, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="rbg")


@partial(jax.jit, static_argnames=("shapes",))
def _draw(key, shapes):
    """Leaves of one shape are drawn as one stacked array and split:
    some 20 draws for any depth."""
    out = {}
    groups: dict[tuple[int, ...], list[str]] = {}
    for name, shape, kind in shapes:
        if kind == "normal":
            groups.setdefault(shape, []).append(name)
        else:
            out[name] = (jnp.ones if kind == "ones" else jnp.zeros)(
                shape, jnp.float32)
    for i, (shape, names) in enumerate(groups.items()):
        block = INIT_STD * jax.random.normal(
            jax.random.fold_in(key, i), (len(names), *shape), jnp.float32)
        for j, name in enumerate(names):
            out[name] = block[j]
    return out


def make_params(cfg: dict, seed: int) -> dict[str, jax.Array]:
    """All leaves in float32 (the type they are served in), on the
    default device, from one jitted call."""
    return _draw(seed_key(seed, 0), tuple(leaf_shapes(cfg)))
