#!/usr/bin/env python3
"""How ``asr_tick.xplane.pb`` and ``asr_tick.summary.json`` were made
(on the chip; PR 26, recorded again in PR 27 when the beam program
changed):

    python3 tests/fixtures/record_asr_trace.py chiprun_out/asr_tick

One 1 x 5 tick of the engine at tiny widths (random weights, a stand-in
tokenizer) under a plain ``jax.profiler`` capture, after a first tick
that compiled the programs: the capture as the profiler wrote it, and
what ``vlog_tpu/obs/profiler.py::summarize`` reads from it. The test
summarizes the kept capture again and compares. Also prints where a
device-op event of this runtime carries its framework name (PERF.md
section 3 records the answer).
"""

import glob
import json
import shutil
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def tiny_assets():
    from vlog_tpu.asr.load import SpecialTokens, WhisperAssets
    from vlog_tpu.asr.model import WhisperConfig, init_random_params

    cfg = WhisperConfig(
        d_model=64, encoder_layers=2, decoder_layers=2,
        encoder_attention_heads=2, decoder_attention_heads=2,
        encoder_ffn_dim=128, decoder_ffn_dim=128, vocab_size=200,
        max_target_positions=32)
    tokens = SpecialTokens(
        sot=100, eot=99, transcribe=102, translate=103, no_timestamps=104,
        timestamp_begin=110, no_speech=105, language_ids={"en": 101},
        suppress=(), begin_suppress=())

    class Tokenizer:
        @staticmethod
        def decode(ids):
            return " ".join(str(i) for i in ids)

    return WhisperAssets(cfg=cfg, params=init_random_params(cfg, seed=26),
                         tokenizer=Tokenizer(), tokens=tokens,
                         model_name="tiny-random")


def one_tick(engine, job: str) -> None:
    t = np.arange(16000 * 5) / 16000.0
    tone = (0.25 * np.sin(2 * np.pi * 220.0 * t)).astype(np.float32)
    handle = engine.begin_job(job, language="en", max_new=12, beam=5)
    handle.submit(0, 0.0, tone)
    list(handle.results())
    handle.close()


def main(out: str) -> None:
    import jax

    from vlog_tpu.asr.engine import AsrEngine
    from vlog_tpu.obs.profiler import summarize

    if jax.devices()[0].platform != "tpu":
        sys.exit("record_asr_trace.py: no TPU")
    engine = AsrEngine(tiny_assets(), batch_windows=1, tick_s=0.01)
    one_tick(engine, "warm")
    log_dir = Path(out + ".tmp")
    shutil.rmtree(log_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    one_tick(engine, "traced")
    jax.profiler.stop_trace()
    engine.close()
    pb = glob.glob(str(log_dir / "plugins" / "profile" / "*"
                       / "*.xplane.pb"))[0]
    Path(out).parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(pb, out + ".xplane.pb")
    got = summarize(out + ".xplane.pb")
    Path(out + ".summary.json").write_text(json.dumps(got, indent=1) + "\n")
    print(json.dumps(got)[:3000])
    print("tick record:", json.dumps(engine.batch_log[-1], default=str))

    data = jax.profiler.ProfileData.from_file(pb)
    for plane in data.planes:
        print("plane", plane.name, [(ln.name, len(list(ln.events)))
                                    for ln in plane.lines][:12])
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            seen = set()
            for ev in line.events:
                if ev.name in seen or len(seen) >= 6:
                    continue
                seen.add(ev.name)
                print("  op", ev.name[:100])
                for k, v in ev.stats:
                    print("     stat", k, "=", str(v)[:160])
    shutil.rmtree(log_dir, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1])
