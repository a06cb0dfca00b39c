"""Operations and bytes worked out from shapes, and the chip's peaks.

Model FLOPs count what the architecture requires: a matmul of (m, k) by
(k, n) is ``2*m*k*n``; attention counts the scores and the weighted sum of
the keys each query may see (causal, inside the window); a training step
is the forward pass plus twice it for the backward pass.  Recomputation
under remat, masked-out attention scores, MoE dispatch/combine einsums
and capacity padding are not counted: an MoE layer counts its router and
the ``experts_per_token`` experts each token is routed to.  Elementwise
work (norms, softmax, RoPE, the optimizer) is not counted either.

Configurations use the keys of the published ``config.json``
(``hidden_size``, ``num_attention_heads``, ...), as in ``configs/*.json``.
"""
from __future__ import annotations

import re
from typing import Dict, Mapping

#: Per-chip peaks, keyed by ``jax.Device.device_kind``.  Source: Google
#: Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
#: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s per chip.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of ``device_kind``; an unknown device raises."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to PEAKS with their "
                       f"source") from None


def _keys_seen(seq: int, window: int) -> int:
    """Sum over query positions ``i < seq`` of ``min(i + 1, window)``."""
    w = min(window or seq, seq)
    return w * (w + 1) // 2 + (seq - w) * w


def forward_flops(cfg: Mapping, batch: int, seq: int) -> int:
    """Model FLOPs of one forward pass over ``batch`` rows of ``seq``
    tokens, logits over the whole vocabulary included."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, V = cfg["head_dim"], cfg["vocab_size"]
    T = batch * seq
    proj = 2 * T * d * (H + 2 * KV) * hd + 2 * T * H * hd * d
    # scores q.k and the weighted sum of values, per visible key
    attn = 2 * (2 * batch * H * hd * _keys_seen(seq, cfg.get(
        "sliding_window") or 0))
    f = cfg["intermediate_size"]
    experts = cfg.get("num_local_experts") or 0
    if experts:
        k = cfg["num_experts_per_tok"]
        mlp = 2 * T * d * experts + 3 * 2 * T * k * d * f
    else:
        mlp = 3 * 2 * T * d * f
    head = 2 * T * d * V
    return L * (proj + attn + mlp) + head


def train_step_flops(cfg: Mapping, batch: int, seq: int) -> int:
    """Forward and backward: three times the forward pass."""
    return 3 * forward_flops(cfg, batch, seq)


_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8}
_SHAPE = re.compile(r"\b(pred|[suf]\d+|bf16)\[([\d,]*)\]")


def hlo_shape_bytes(text: str) -> int:
    """Bytes of every array shape written in HLO text such as
    ``(f32[8,128]{1,0}, f32[8,128]{1,0}) custom-call(s8[4,8,128]{...})``:
    a kernel call's operands and results, each counted once."""
    total = 0
    for dtype, dims in _SHAPE.findall(text):
        n = 1
        for x in filter(None, dims.split(",")):
            n *= int(x)
        total += n * _DTYPE_BYTES[dtype]
    return total
