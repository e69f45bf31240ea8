"""Operations and bytes the algorithms NEED, as functions of shapes.

Every share of a peak the benchmark reports divides one of these by a
time from the device trace. They count what the mathematics requires and
nothing a particular implementation adds: recomputed matmuls, a forward
kernel run twice, logits at positions that carry no label. A count that is too high reads as a share over 100 %.
"""
from __future__ import annotations


def _enc_layer_matmul_flops_per_token(hidden: int, intermediate: int) -> int:
    # QKV (H x 3H), attention output (H x H), FFN in and out (H x I, I x H);
    # 2 FLOPs per multiply-add
    return 2 * (4 * hidden * hidden + 2 * hidden * intermediate)


def attention_flops_per_token(seq: int, hidden: int, causal: bool) -> float:
    """QK^T and PV for one query token against `seq` keys over all heads
    (heads x head_dim = hidden): 2 x 2 x seq x hidden, halved when causal."""
    f = 4.0 * seq * hidden
    return f / 2 if causal else f


def bert_train_flops_per_token(*, hidden: int, intermediate: int, layers: int,
                               vocab: int, seq: int,
                               label_share: float) -> float:
    """Forward + backward FLOPs per processed position of one MLM step.

    Backward is twice the forward (a gradient for each operand of each
    matmul). Embedding lookups are reads, not matmuls. The decoder
    (hidden x vocab) is needed only at labelled positions
    (`label_share` of all positions), as the published BERT gathers them.
    """
    fwd = layers * (_enc_layer_matmul_flops_per_token(hidden, intermediate)
                    + attention_flops_per_token(seq, hidden, causal=False))
    fwd += label_share * 2.0 * hidden * vocab
    return 3.0 * fwd


def flash_train_flops_bytes(*, batch: int, heads: int, seq: int,
                            head_dim: int, layers: int, causal: bool,
                            dtype_bytes: int = 2) -> tuple:
    """(FLOPs, HBM bytes) the attention of one train step needs, all layers.

    Forward: S = QK^T, O = PV (2 matmuls). Backward: dV = P^T dO,
    dP = dO V^T, dQ = dS K, dK = dS^T Q (4 matmuls); recomputing P inside
    the backward kernels is the implementation's choice and is not counted.
    Bytes: forward reads Q, K, V and writes O; backward reads Q, K, V, O,
    dO and writes dQ, dK, dV; each is batch x seq x heads x head_dim.
    """
    one_matmul = 2.0 * batch * heads * seq * seq * head_dim
    if causal:
        one_matmul /= 2
    flops = layers * 6.0 * one_matmul
    tensor = batch * seq * heads * head_dim * dtype_bytes
    return flops, layers * 12.0 * tensor


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(least seconds the chip could take, which bound it is)."""
    t_f = flops / peaks["bf16_flops"]
    t_b = nbytes / peaks["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")
