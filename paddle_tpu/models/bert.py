"""BERT/ERNIE-family transformer encoder, static-graph builder.

Reference counterpart: the fluid.layers transformer used by the reference's
dist_transformer.py test model and ERNIE pretraining (BASELINE configs 3/4).
Built TPU-first: bf16-friendly, batch-major [B, S, H], and ships Megatron
sharding rules (column-parallel QKV/FFN-in, row-parallel proj/FFN-out) as
data for the SPMD executor. Attention lowers to the fused `attention` op
(pallas flash-attention kernel on TPU when available, ops/attention.py).
"""
from __future__ import annotations

import math
from dataclasses import dataclass

from jax.sharding import PartitionSpec as P

from .. import layers
from ..layer_helper import ParamAttr
from ..framework.program import name_scope
from ..observability.trace import RecordEvent
from .. import initializer as I
from ..parallel.mesh import ShardingRules


@dataclass
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position: int = 512
    type_vocab_size: int = 2
    hidden_dropout: float = 0.1
    attention_dropout: float = 0.1
    seq_len: int = 128
    sequence_parallel: bool = False   # ring attention over the sp mesh axis
    sp_mode: str = "ring"
    moe_experts: int = 0              # >0: switch-MoE FFN (ep mesh axis)
    moe_capacity_factor: float = 2.0
    # >0: annotate device_guard stages for pipeline parallelism over the pp
    # mesh axis (embeddings stage 0, layers round-robin, head last stage)
    pipeline_stages: int = 0
    # MLM head as the vocab-chunked streaming CE over the labelled rows
    # (ops/fused_ce.py). None = auto: at a real vocab (>= 2x the chunk),
    # whatever the sequence length — the op computes only the rows that
    # carry a label, a seventh of a masked LM's, where the dense pair
    # computes and writes [B, S, V] float32 logits for all of them.
    # True/False forces.
    fused_mlm_head: "bool | None" = None

    @staticmethod
    def base():
        return BertConfig()

    @staticmethod
    def large():
        return BertConfig(hidden_size=1024, num_layers=24, num_heads=16,
                          intermediate_size=4096)

    @staticmethod
    def tiny():
        return BertConfig(vocab_size=1024, hidden_size=64, num_layers=2,
                          num_heads=4, intermediate_size=128,
                          max_position=128, seq_len=32)


def _attr(name):
    return ParamAttr(name=name, initializer=I.TruncatedNormal(0.0, 0.02))


def encoder_layer(x, cfg: BertConfig, idx: int, attn_mask=None):
    """One transformer block. Param names carry qkv/proj/ffn markers that the
    TP sharding rules key on. Scopes `attn.proj`, `attn.attend.full`,
    `ffn.dense` (`moe.switch` with experts); the dropouts, residual adds
    and layer norms carry the caller's `layer.residual`."""
    h = cfg.hidden_size
    nh = cfg.num_heads
    hd = h // nh
    pre = x

    def heads(t):
        # cut where the projection's rows lie: the attention op takes
        # [B, S, nh, hd] (its layout "bshd") and no transpose is built
        return layers.reshape(t, [0, 0, nh, hd])

    with name_scope("attn.proj"):
        # fused QKV projection (one MXU matmul instead of three)
        qkv = layers.fc(x, 3 * h, num_flatten_dims=2,
                        param_attr=_attr(f"enc{idx}_attn_qkv_w"),
                        bias_attr=ParamAttr(name=f"enc{idx}_attn_qkv_b"))
        q, k, v = layers.split(qkv, 3, dim=2)
        q, k, v = heads(q), heads(k), heads(v)
    # sp and non-sp train with the SAME dropout/mask semantics (round 4:
    # the ring/ulysses paths take key-padding masks + counter dropout)
    with name_scope("attn.attend.full"):
        ctx = layers.fused_attention(
            q, k, v, mask=attn_mask, scale=1.0 / math.sqrt(hd),
            dropout=cfg.attention_dropout,
            sequence_parallel=cfg.sequence_parallel, sp_mode=cfg.sp_mode,
            layout="bshd")
    with name_scope("attn.proj"):
        ctx = layers.reshape(ctx, [0, 0, h])
        proj = layers.fc(ctx, h, num_flatten_dims=2,
                         param_attr=_attr(f"enc{idx}_attn_proj_w"),
                         bias_attr=ParamAttr(name=f"enc{idx}_attn_proj_b"))
    if cfg.hidden_dropout:
        proj = layers.dropout(proj, cfg.hidden_dropout,
                              dropout_implementation="upscale_in_train")
    x = layers.layer_norm(layers.elementwise_add(pre, proj),
                          begin_norm_axis=2,
                          param_attr=ParamAttr(name=f"enc{idx}_ln1_scale"),
                          bias_attr=ParamAttr(name=f"enc{idx}_ln1_bias"))

    pre = x
    aux = None
    if cfg.moe_experts > 0:
        # switch-MoE FFN: experts shard over the ep mesh axis (ops/moe.py)
        with name_scope("moe.switch"):
            ffn, aux = layers.switch_moe(
                x, num_experts=cfg.moe_experts, d_ff=cfg.intermediate_size,
                capacity_factor=cfg.moe_capacity_factor,
                name=f"enc{idx}_moe")
    else:
        with name_scope("ffn.dense"):
            ffn = layers.fc(x, cfg.intermediate_size, num_flatten_dims=2,
                            act="gelu",
                            param_attr=_attr(f"enc{idx}_ffn_in_w"),
                            bias_attr=ParamAttr(name=f"enc{idx}_ffn_in_b"))
            ffn = layers.fc(ffn, h, num_flatten_dims=2,
                            param_attr=_attr(f"enc{idx}_ffn_out_w"),
                            bias_attr=ParamAttr(name=f"enc{idx}_ffn_out_b"))
    if cfg.hidden_dropout:
        ffn = layers.dropout(ffn, cfg.hidden_dropout,
                             dropout_implementation="upscale_in_train")
    out = layers.layer_norm(layers.elementwise_add(pre, ffn),
                            begin_norm_axis=2,
                            param_attr=ParamAttr(name=f"enc{idx}_ln2_scale"),
                            bias_attr=ParamAttr(name=f"enc{idx}_ln2_bias"))
    return (out, aux) if cfg.moe_experts > 0 else out


def bert_encoder(input_ids, cfg: BertConfig, position_ids=None,
                 attn_mask=None):
    """Embeddings + N encoder layers -> sequence output [B, S, H]. With
    moe_experts>0, per-layer aux load-balancing losses accumulate on the
    returned var's `_moe_aux_losses` (build_pretrain_program adds them)."""
    aux_losses = []
    ckpts = []
    stage = _stage_guard(cfg)
    with stage(0), name_scope("embed.tokens"):
        x = _bert_embeddings(input_ids, cfg)
    for i in range(cfg.num_layers):
        with stage(_layer_stage(cfg, i)), name_scope("layer.residual"):
            x = encoder_layer(x, cfg, i, attn_mask)
        if cfg.moe_experts > 0:
            x, aux = x
            aux_losses.append(aux)
        ckpts.append(x.name)
    x._moe_aux_losses = aux_losses
    # per-layer boundary vars: the natural RecomputeOptimizer checkpoints
    x._layer_checkpoints = ckpts
    return x


def _stage_guard(cfg: BertConfig):
    """device_guard factory: a no-op context when pipeline is off."""
    import contextlib
    from ..framework.program import device_guard
    if cfg.pipeline_stages and cfg.pipeline_stages > 1:
        return lambda s: device_guard(f"gpu:{s}")
    return lambda s: contextlib.nullcontext()


def _layer_stage(cfg: BertConfig, i: int) -> int:
    if not cfg.pipeline_stages or cfg.pipeline_stages <= 1:
        return 0
    if cfg.pipeline_stages > cfg.num_layers:
        raise ValueError(
            f"pipeline_stages={cfg.pipeline_stages} > num_layers="
            f"{cfg.num_layers}: some pp submeshes would hold no ops")
    return i * cfg.pipeline_stages // cfg.num_layers


def _last_stage(cfg: BertConfig) -> int:
    return max(1, cfg.pipeline_stages or 1) - 1


def _bert_embeddings(input_ids, cfg: BertConfig):
    word_emb = layers.embedding(
        layers.unsqueeze(input_ids, [2]), [cfg.vocab_size, cfg.hidden_size],
        param_attr=_attr("word_embedding"))
    word_emb = layers.reshape(word_emb, [0, 0, cfg.hidden_size])
    pos_emb_table = layers.create_parameter(
        [cfg.max_position, cfg.hidden_size], "float32",
        attr=_attr("pos_embedding"))
    pos_emb = layers.slice(pos_emb_table, [0], [0], [cfg.seq_len])
    pos_emb = layers.unsqueeze(pos_emb, [0])
    x = layers.elementwise_add(word_emb, pos_emb)
    x = layers.layer_norm(x, begin_norm_axis=2,
                          param_attr=ParamAttr(name="emb_ln_scale"),
                          bias_attr=ParamAttr(name="emb_ln_bias"))
    if cfg.hidden_dropout:
        x = layers.dropout(x, cfg.hidden_dropout,
                           dropout_implementation="upscale_in_train")
    return x


def _tp_vocab_shards_head() -> bool:
    """True when the active mesh tensor-parallelizes and this model's TP
    rules vocab-shard `mlm_head_w` (P(None, 'tp') on the [H, V] fc weight):
    the fused head's chunked scan would make GSPMD regather the sharded
    weight per chunk, undoing the Megatron vocab-parallel head — so the
    AUTO-select must stay dense there (forcing fused_mlm_head=True still
    wins). Reads the CURRENTLY-set mesh, so it only covers builds that run
    after fleet.init/set_mesh; for the build-then-init order the
    auto-selected op carries an `auto_selected` attr and
    DistributedOptimizer.minimize warns when tp rules will shard it
    (distributed/fleet/base.py) — force `fused_mlm_head=False` there."""
    from ..parallel.mesh import get_mesh
    mesh = get_mesh()
    if mesh is None or int(mesh.shape.get("tp", 1)) <= 1:
        return False
    spec = tp_sharding_rules().spec_for("mlm_head_w")
    return any(ax == "tp" or (isinstance(ax, (tuple, list)) and "tp" in ax)
               for ax in spec)


def bert_pretrain_loss(seq_out, mlm_labels, cfg: BertConfig):
    """Masked-LM head + loss (ERNIE pretraining objective).

    With `cfg.fused_mlm_head` (auto at a real vocab, at any sequence
    length, and only when tensor parallelism does not vocab-shard the
    head weight — `_tp_vocab_shards_head`) the head runs as the
    vocab-chunked fused_lm_head_ce (ops/fused_ce.py), which computes only
    the rows that carry a label (as the published BERT gathers the masked
    positions before its head) and never materializes the [B, S, V]
    logits — same parameter names/shapes as the dense fc head, so
    checkpoints are interchangeable. A tiny vocabulary keeps the dense
    pair. Label contract is identical on both paths for the default
    ignore_index (-100): ignored tokens contribute zero loss and zero
    grads."""
    from ..ops.fused_ce import DEFAULT_CHUNK
    fused = cfg.fused_mlm_head
    if fused is None:
        fused = (cfg.vocab_size >= 2 * DEFAULT_CHUNK
                 and not _tp_vocab_shards_head())
    with _stage_guard(cfg)(_last_stage(cfg)), name_scope("head.mlm"):
        if fused:
            hidden = cfg.hidden_size
            w = layers.create_parameter([hidden, cfg.vocab_size],
                                        "float32",
                                        attr=_attr("mlm_head_w"))
            b = layers.create_parameter([cfg.vocab_size], "float32",
                                        attr=ParamAttr(name="mlm_head_b"),
                                        is_bias=True)
            loss = layers.fused_lm_head_ce(seq_out, w, mlm_labels,
                                           bias=b, w_layout="hv")
            if cfg.fused_mlm_head is None:
                # auto-selected (not user-forced): lets minimize warn if
                # tp rules later vocab-shard the head weight
                loss.block.ops[-1].attrs["auto_selected"] = True
        else:
            logits = layers.fc(seq_out, cfg.vocab_size,
                               num_flatten_dims=2,
                               param_attr=_attr("mlm_head_w"),
                               bias_attr=ParamAttr(name="mlm_head_b"))
            loss = layers.softmax_with_cross_entropy(logits, mlm_labels)
        return layers.mean(loss)


def build_pretrain_program(cfg: BertConfig, use_input_mask=False):
    """Declare data vars + full pretrain graph; returns (ids, labels, loss).

    With `use_input_mask`, a float `input_mask` feed (1 = real token,
    0 = pad, shape [B, S]) becomes an additive [-1e9/0] key-padding mask
    [B,1,1,S] that rides into the attention kernels — the padded-batch
    real-data path (reference: bert_encoder_functor.cu masks in-kernel)."""
    with RecordEvent("program.build", args={"model": "bert"}):
        input_ids = layers.data(name="input_ids", shape=[cfg.seq_len],
                                dtype="int64")
        mlm_labels = layers.data(name="mlm_labels", shape=[cfg.seq_len, 1],
                                 dtype="int64")
        attn_mask = None
        if use_input_mask:
            input_mask = layers.data(name="input_mask", shape=[cfg.seq_len],
                                     dtype="float32")
            with name_scope("attn.mask"):
                attn_mask = layers.unsqueeze(
                    layers.scale(input_mask, scale=1e9, bias=-1e9), [1, 2])
        seq = bert_encoder(input_ids, cfg, attn_mask=attn_mask)
        loss = bert_pretrain_loss(seq, mlm_labels, cfg)
        aux = getattr(seq, "_moe_aux_losses", None)
        if aux:   # switch_moe load-balancing term (Switch eq. 4, scale 0.01)
            with name_scope("head.mlm"):
                loss = layers.elementwise_add(
                    loss, layers.scale(layers.sums(aux), 0.01 / len(aux)))
        loss._layer_checkpoints = getattr(seq, "_layer_checkpoints", [])
    return input_ids, mlm_labels, loss


def tp_sharding_rules() -> ShardingRules:
    """Megatron-style tensor-parallel rules: the shared transformer table
    (parallel/mesh.py transformer_tp_rules) + vocab-sharded embeddings and
    MLM head."""
    from ..parallel.mesh import transformer_tp_rules
    return transformer_tp_rules(extra=[
        (r"^word_embedding$", P("tp", None)),
        (r"^mlm_head_w$", P(None, "tp")),
        (r"^mlm_head_b$", P("tp")),
    ])


# ERNIE is architecture-compatible (BASELINE config 4)
ErnieConfig = BertConfig
ernie_encoder = bert_encoder
