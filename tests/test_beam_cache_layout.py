"""What the TPU compiler makes of the beam program's carried self-K/V.

The generate program states the physical layout of the cache it carries
through its scan (asr/decode.py ``_pinned``): left alone, the compiler
puts ``max_len`` in the lanes (the attention products want it there),
one position is then one lane of every tile of a layer, and each of a
step's ``2 x layers`` one-position writes is a read-modify-write of the
whole layer (119 us each on the chip, PERF.md section 6, PR 27 and
PR 32). tests/test_beam_cache.py counts the program's own equations on
the CPU; this file compiles the program for a described v5e (no chip
attached, nothing runs) at Whisper-small's widths with 2 + 2 layers and
2 x 5 rows and reads the optimized HLO of the scan's body. Transposing
the logical shape alone would pass the CPU test and fail here: the
compiler assigns ``max_len`` minor-most again.

The topology is described inside a fixture (the TPU's library belongs
to one process at a time: tests/benchmark_checks/test_benchmark_sizes.py
is the only other file that loads it, and the driver's command lets
both).
"""

from __future__ import annotations

import os
import re

import pytest

LAYERS, WINDOWS, BEAM = 2, 2, 5
D_MODEL, HEADS, VOCAB_SIZE, MAX_TARGET = 768, 12, 51865, 448
PROMPT_LEN = 3
MAX_NEW = MAX_TARGET // 2
MAX_LEN = PROMPT_LEN + MAX_NEW
ROWS = WINDOWS * BEAM
CACHE_ELEMS = LAYERS * ROWS * MAX_LEN * D_MODEL
SLAB_ELEMS = ROWS * D_MODEL


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.fixture(scope="module")
def scan_body(one_chip, no_compile_cache):
    """The instructions of the beam program's scan body, compiled for
    the described chip at the precision the cells state: (name, shapes
    of the output, opcode, operand names, the line), and every
    computation of the module by name."""
    import jax
    import jax.numpy as jnp

    from vlog_tpu.asr.decode import _generate_beam_jit
    from vlog_tpu.asr.model import (DecoderCache, WhisperConfig,
                                    random_state_dict)

    cfg = WhisperConfig(
        d_model=D_MODEL, encoder_layers=LAYERS, decoder_layers=LAYERS,
        encoder_attention_heads=HEADS, decoder_attention_heads=HEADS,
        encoder_ffn_dim=4 * D_MODEL, decoder_ffn_dim=4 * D_MODEL,
        vocab_size=VOCAB_SIZE, max_target_positions=MAX_TARGET)

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = {n: s(w.shape) for n, w in random_state_dict(cfg).items()}
    page = s((LAYERS, ROWS, HEADS, MAX_LEN, D_MODEL // HEADS))
    with jax.default_matmul_precision("highest"):
        compiled = _generate_beam_jit.lower(
            params, s((WINDOWS, cfg.num_mel_bins, 3000)),
            s((PROMPT_LEN,), jnp.int32), s((VOCAB_SIZE,)), s((VOCAB_SIZE,)),
            DecoderCache(k=page, v=page), cfg=cfg, sot=50258, eot=50257,
            ts_begin=50364, no_speech=50362, max_new=MAX_NEW,
            timestamps=True, beam=BEAM).compile()
    comps = _computations(compiled.as_text())
    bodies = [comps[m.group(1)] for lines in comps.values() for _, _, op, _,
              line in lines if op == "while"
              for m in [re.search(r"body=%?([\w.\-]+)", line)]]
    # the scan is the while with the largest body (the encoder has none)
    return max(bodies, key=len), comps


_SHAPE = re.compile(r"(\w+)\[([\d,]*)\](?:\{([\d,]*)[^}]*\})?")
_INSTR = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\((.*)$")


def _computations(text: str) -> dict[str, list[tuple]]:
    comps: dict[str, list[tuple]] = {}
    cur = None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            m = _INSTR.match(line)
            if m:
                name, shape, op, rest = m.groups()
                operands = re.findall(r"%([\w.\-]+)", rest.split("), ")[0])
                cur.append((name, _shapes(shape), op, operands, line))
    return comps


def _shapes(text: str) -> list[tuple[tuple[int, ...], tuple[int, ...]]]:
    """(dimensions, minor-to-major order) of every array in a shape."""
    return [(tuple(int(d) for d in dims.split(",") if d),
             tuple(int(d) for d in (order or "").split(",") if d))
            for _, dims, order in _SHAPE.findall(text)]


def _elems(dims: tuple[int, ...]) -> int:
    n = 1
    for d in dims:
        n *= d
    return n


_NO_OUTPUT_OF_THEIR_OWN = ("get-tuple-element", "parameter", "tuple",
                          "bitcast")


def _cache_sized(body, comps) -> list[dict]:
    """Every instruction of the body with an output as large as one
    carried array: its name, dimensions, minor-to-major order, what it
    is (a fusion is named by its root) and the dimensions of the root's
    second operand (a ``dynamic-update-slice``'s update)."""
    by_name = {name: shapes for name, shapes, *_ in body}
    found = []
    for name, shapes, op, operands, line in body:
        if op in _NO_OUTPUT_OF_THEIR_OWN:
            continue
        for dims, order in shapes:
            if _elems(dims) != CACHE_ELEMS:
                continue
            shapes_of = by_name
            if op == "fusion":
                called = re.search(r"calls=%?([\w.\-]+)", line).group(1)
                inner = comps[called]
                (_, _, op, operands, _), = [i for i in inner
                                            if i[4].lstrip().startswith("ROOT ")]
                shapes_of = {n: sh for n, sh, *_ in inner}
            second = shapes_of.get(operands[1]) if len(operands) > 1 else None
            found.append(dict(name=name, dims=dims, order=order, op=op,
                              update=second[0][0] if second else None,
                              line=line[:240]))
    return found


def test_nothing_in_the_scan_body_is_as_large_as_the_cache_but_its_writes(
        scan_body):
    """The body's only cache-sized outputs are the ``2 x layers``
    in-place writes, each a ``dynamic-update-slice`` (alone, or the root
    of a fusion with the projection that feeds it): no copy, transpose,
    select or broadcast of a whole carried array."""
    big = _cache_sized(*scan_body)
    assert [w["op"] for w in big] == ["dynamic-update-slice"] * (2 * LAYERS), [
        w["line"] for w in big]


def test_a_step_writes_one_position_and_positions_are_not_the_lanes(
        scan_body):
    """The array a step writes into does not have the position axis
    minor-most (one position would be one lane of every tile of the
    layer), and what each write puts there is one position of every
    row: ``rows x d_model`` elements, extent 1 along the position
    axis."""
    writes = [w for w in _cache_sized(*scan_body)
              if w["op"] == "dynamic-update-slice"]
    assert writes
    for w in writes:
        dims, order, update = w["dims"], w["order"], w["update"]
        assert dims.count(MAX_LEN) == 1, dims
        position = dims.index(MAX_LEN)
        assert order and order[0] != position, (
            f"{w['name']}: the position axis is minor-most in {dims} "
            f"{order}")
        assert len(update) == len(dims) and update[position] == 1, w
        assert _elems(update) == SLAB_ELEMS, w
