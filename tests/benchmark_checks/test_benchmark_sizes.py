"""The cells' beam programs compiled for a described v5e chip (no chip
attached, nothing runs): each fits the chip and clears the driver's
4 GiB floor, so the cells' sizes are guarded at no chip time.

``memory_analysis()`` counts arguments (weights, the cache page in),
outputs (the cache page out, not aliased) and temporaries. On the chip
the allocator shows the same total in two parts (my chip run, PR 25, 8 x 5
``small``): 2.77 GB ``peak_bytes_in_use`` (arguments and outputs) and
3.63 GB ``peak_bytes_reserved`` (the loaded program's temporaries), 6.40
GB together against 6.24 GB here. The benchmark reports their sum as
``memory_peak_bytes`` (PERF.md, section 2).
"""

import json
import os
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmark"
HBM = 17_179_869_184
GIB = 1 << 30


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


def _compile_beam_program(config_name: str, sharding):
    import jax
    import jax.numpy as jnp

    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))
    from models.whisper_weights import leaf_shapes
    from vlog_tpu.asr.decode import _generate_beam_jit
    from vlog_tpu.asr.model import DecoderCache, WhisperConfig

    cfg = json.loads((BENCH / "configs" / f"{config_name}.json").read_text())
    env = cfg["deployment"]["env"]
    windows, beam = int(env["VLOG_ASR_BATCH_WINDOWS"]), int(
        env["VLOG_WHISPER_BEAM"])
    wc = WhisperConfig.from_hf(cfg)
    max_new = wc.max_target_positions // 2
    hd = wc.d_model // wc.decoder_attention_heads

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    params = {n: s(shape) for n, shape, _ in leaf_shapes(cfg)}
    page = s((wc.decoder_layers, windows * beam,
              wc.decoder_attention_heads, 3 + max_new, hd))
    voc = cfg["vocab"]
    compiled = _generate_beam_jit.lower(
        params, s((windows, wc.num_mel_bins, 3000)), s((3,), jnp.int32),
        s((wc.vocab_size,)), s((wc.vocab_size,)),
        DecoderCache(k=page, v=page), cfg=wc, sot=voc["sot"], eot=voc["eot"],
        ts_begin=voc["timestamp_begin"], no_speech=voc["no_speech"],
        max_new=max_new, timestamps=True, beam=beam).compile()
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes), m


@pytest.mark.parametrize("config_name,least_gib", [
    ("whisper_small", 4.0),      # 8 x 5: ISSUE 25 read 6.2 GB
    ("whisper_medium", 4.0),     # 4 x 5: ISSUE 25 read 11.3 GB
])
def test_cell_program_fits_the_chip_and_clears_the_floor(
        config_name, least_gib, one_chip, no_compile_cache):
    total, m = _compile_beam_program(config_name, one_chip)
    print(config_name, total, m)
    assert total < HBM, f"{config_name}: {total} bytes do not fit {HBM}"
    assert total > least_gib * GIB, (
        f"{config_name}: the beam program counts {total / GIB:.2f} GiB, "
        f"under the {least_gib} GiB the cell was sized to")
