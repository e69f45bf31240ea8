"""Model FLOP/s utilization: `train_tok_s` times the operations one
position needs (benchmark/counts.py: matmuls of the encoder, non-causal
attention, the decoder at labelled positions; lookups and recomputation
not counted) over chips times the bf16 peak."""
from benchmark import counts, traffic


def read(ctx):
    if ctx["kind"] != "train":
        return None
    cfg = ctx["cfg"]
    per_token = counts.bert_train_flops_per_token(
        hidden=cfg["hidden_size"], intermediate=cfg["intermediate_size"],
        layers=cfg["num_hidden_layers"], vocab=cfg["vocab_size"],
        seq=ctx["seq"],
        label_share=traffic.train_label_share(ctx["spec"], ctx["rows"]))
    return 100.0 * ctx["train_tok_s"] * per_token / (
        ctx["chips"] * ctx["peaks"]["bf16_flops"])
