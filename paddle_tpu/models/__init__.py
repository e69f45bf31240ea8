"""Model zoo covering the BASELINE workload configs (BASELINE.md):
1. LeNet (MNIST, static graph)      -> lenet.py
2. ResNet-50 (dygraph paddle.nn)    -> resnet.py
3/4. BERT/ERNIE transformer (static, SPMD-ready with TP rules) -> bert.py
5. Wide&Deep CTR (sparse embeddings) -> wide_deep.py
Plus a GPT-style causal-decoder LM (tied embeddings, pre-LN, causal flash
attention, TP rules) -> gpt.py, and SE-ResNeXt 50/101/152 (the reference's
canonical dist-test model, grouped convs + squeeze-excitation)
-> se_resnext.py, and a DeepSeek-V3-family sparse causal LM (latent
attention, sigmoid-routed experts without drops, shared experts, one
expert-parallel rank's share) -> deepseek_v3.py, and a Mellum-2-family
sparse causal LM (sliding-window and full attention layers in a period,
grouped KV heads, yarn on the full layers, softmax-routed experts)
-> mellum.py, and a Nemotron-H-family hybrid causal LM (a Mamba-2
state-space mixer, ungated relu^2 experts with a shared one, or attention
without rotary positions a layer, by a pattern string) -> nemotron_h.py,
and a Ling-3.0-family hybrid causal LM (Kimi-delta linear attention with
latent attention in the last layer of every group, head-wise output gates,
sigmoid-routed experts picked inside the best groups, a chip's share of the
heads as of the experts) -> ling.py
"""
from . import (lenet, resnet, bert, wide_deep, gpt, se_resnext, deepseek_v3,
               mellum, nemotron_h, ling)
