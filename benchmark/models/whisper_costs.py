"""Operations and bytes that one engine tick's ALGORITHM needs, from
shapes alone. The yardstick of ``asr_mfu_pct`` and
``asr_program_roofline``; it does not change with what implements the
tick, so a faster program reads a higher share and none can pass 100%.

One tick transcribes ``windows`` 30 s windows with ``beams`` beams:
log-mel, the encoder and the cross-attention K/V once per WINDOW, three
prompt steps per window (the beams are identical until the first
generated token), then ``steps`` decoder steps over windows x beams
rows, each attending to its own cache prefix and to its window's
cross-K/V, and a log-softmax over the vocabulary per row and step.

Needed bytes are what has to cross HBM at least once: per decoder step
the decoder's weights and the output embedding once, the cross-K/V of
the windows once (not once per beam), each row's self-K/V prefix once
plus the new entry, and the logits once; per tick the encoder's weights
once, the mel in and the encoder states out, and the cross-K/V written.
Activations inside a layer are taken to stay on chip.
"""

from __future__ import annotations

import math

N_FRAMES = 3000
N_FFT = 400
N_FREQ = 201


def _layer_params(d: int, ffn: int, cross: bool) -> int:
    attn = 4 * d * d + 3 * d              # q, k, v, o and three biases
    n = attn + 2 * d                      # + its layer norm
    if cross:
        n += attn + 2 * d
    return n + 2 * d * ffn + ffn + d + 2 * d


def tick_cost(cfg: dict, *, windows: int, beams: int, steps: int,
              prompt_len: int = 3, weight_bytes: int = 4,
              act_bytes: int = 4) -> dict:
    """``{"flops", "bytes", "parts": {...}}`` for one tick."""
    d = cfg["d_model"]
    ffn_e, ffn_d = cfg["encoder_ffn_dim"], cfg["decoder_ffn_dim"]
    le, ld = cfg["encoder_layers"], cfg["decoder_layers"]
    v, mels = cfg["vocab_size"], cfg["num_mel_bins"]
    t = cfg["max_source_positions"]
    rows = windows * beams

    mel = windows * (N_FRAMES * 5 * N_FFT * math.log2(N_FFT)
                     + 2 * N_FRAMES * N_FREQ * mels)
    conv = windows * (2 * 3 * mels * d * N_FRAMES + 2 * 3 * d * d * t)
    enc_layer = 8 * t * d * d + 4 * t * t * d + 4 * t * d * ffn_e
    enc = conv + windows * le * enc_layer
    ckv = windows * ld * 4 * t * d * d

    def dec_step(n_rows: int, ctx: int) -> float:
        per_layer = (8 * d * d + 4 * ctx * d        # self attention
                     + 4 * d * d + 4 * t * d        # cross attention
                     + 4 * d * ffn_d)
        return n_rows * (ld * per_layer + 2 * d * v + 5 * v)

    prompt = sum(dec_step(windows, p + 1) for p in range(prompt_len))
    gen = sum(dec_step(rows, prompt_len + s + 1) for s in range(steps))
    flops = mel + enc + ckv + prompt + gen

    enc_w = (3 * mels * d + d + 3 * d * d + d + t * d
             + le * _layer_params(d, ffn_e, False) + 2 * d)
    dec_w = (ld * _layer_params(d, ffn_d, True) + 2 * d
             + v * d + cfg["max_target_positions"] * d)
    ckv_bytes = windows * ld * 2 * t * d * act_bytes
    tick_bytes = (enc_w * weight_bytes
                  + windows * (mels * N_FRAMES + t * d) * act_bytes
                  + ckv_bytes)

    def step_bytes(n_rows: int, ctx: int) -> float:
        self_kv = n_rows * ld * 2 * (ctx + 1) * d * act_bytes
        return (dec_w * weight_bytes + ckv_bytes + self_kv
                + n_rows * v * act_bytes)

    dec_bytes = (sum(step_bytes(windows, p) for p in range(prompt_len))
                 + sum(step_bytes(rows, prompt_len + s)
                       for s in range(steps)))
    return {"flops": float(flops), "bytes": float(tick_bytes + dec_bytes),
            "parts": {"mel_flops": float(mel), "encoder_flops": float(enc),
                      "cross_kv_flops": float(ckv),
                      "decoder_flops": float(prompt + gen),
                      "encoder_bytes": float(tick_bytes),
                      "decoder_bytes": float(dec_bytes)}}


def least_seconds(cost: dict, peaks: dict) -> tuple[float, str]:
    """The roofline: the larger of operations over peak and bytes over
    bandwidth, and which of the two it is."""
    by_flops = cost["flops"] / peaks["flops_per_s"]
    by_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return (by_flops, "flops") if by_flops >= by_bytes \
        else (by_bytes, "bytes")
