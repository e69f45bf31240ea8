"""Every builder behind a benchmark cell, at its tiny preset: the float32
train step it traces and the Programs it builds are the ones of commit a347763
(PR 41, jax 0.9.0), before `models/causal_lm.py` took what the four sparse
builders shared. One table: a refactor of the builders, or a change to an op
that should leave other cells alone, is held here for all of them; a
deliberate change to a step changes its row (print `tiny_step_digests` of
the case twice, compare, paste). PR 49 changed every row's Programs digest
and no step's: the builders name the ops they had left bare (`embed.tokens`,
`layer.residual`, `ffn.dense`, `head.*`, `moe.io`, ...), and with every
`name_scope` attribute cut the Programs of both trees hashed alike.
PR 52 changed both digests of the "bert" row and of no other: BERT's encoder
cuts its projections into heads by a reshape alone and passes
`fused_attention` the attr `layout="bshd"`, so the two `transpose` ops a
side of the attention op are gone from its Programs and the dense route's
transposes are the op's own in its step; every causal-LM builder transposes
as before and is held where it was.
"""
import numpy as np
import pytest

from causal_lm_harness import causal_lm, tiny_step_digests

from paddle_tpu.models import (bert, deepseek_v3, keye, lfm2, ling, mellum,
                               nemotron_h, solar)


def _bert_pretrain():
    cfg = bert.BertConfig.tiny()
    _, _, loss = bert.build_pretrain_program(cfg)
    return loss, {"input_ids": np.zeros((2, 2, cfg.seq_len), np.int64),
                  "mlm_labels": np.zeros((2, 2, cfg.seq_len, 1), np.int64)}


# case: (build, the step's jaxpr, the main and startup Programs)
_STEPS = {
    "bert": (_bert_pretrain,
        "6804e6086d6657fd179e5167d7d49fce97678ade398e529c612cc73ff0ed9727",
        "363981fcb99f2991b9f90180cf10f439b4be2fa5bbb6ebefd0aa23331c3e4074"),
    "kanana": (causal_lm(deepseek_v3, deepseek_v3.DeepseekV3Config.tiny()),
        "b55dd5734b173553d7c9752e5e345b292002dc0118da0dfaf078759bc831ca74",
        "7629d21e97e3faa230d9fea538a8620a391870d7f569090b1d2630c3e6a277e6"),
    "mellum": (causal_lm(mellum, mellum.MellumConfig.tiny()),
        "ece5b162f3f2d703cacc41a43320bc2ae04f46f1dfcef901271578999e044e88",
        "5b3fce692ad51dae761ef43d59e195e2f57808da31a8aa89f98f1689fe34e84e"),
    "hybrid": (causal_lm(nemotron_h, nemotron_h.NemotronHConfig.tiny()),
        "dc9e4d6bbb4513b2741e514caf9d4b7195cf9afe151839d818cf5f3bd66c00c9",
        "1919c81eb8e7e15c09d7e713a70a7457b4abc3a22a962f6bea677f512beea435"),
    "latent_hybrid": (causal_lm(
        nemotron_h, nemotron_h.NemotronHConfig.tiny_latent_share()),
        "ce94d43b5b24963e07c270f8de8b711da0ef8d8ea3d4a1909f00d4de6d174779",
        "b386de2cac892bcce0a6ce879279d0c0d29f3c36288c8ddd0e6c44f5ae746174"),
    # PR 51 changed this row's Programs digest and not its step's: the
    # builder hands `kda_scan` its `kda_lower_bound` (the attr `lower_bound`,
    # which keeps the op on the form the step had)
    "ling": (causal_lm(ling, ling.LingConfig.tiny()),
        "60c17202fc73f82ce61d96a22f7176830b0d9bb93b04242555d4f6854dc22072",
        "bbf99a35aa77935087ad52f1b9fb4025bfb272668a14d1c4fcfb9eeaf605fa74"),
    # made at PR 43, which brought the builder: held from here on
    "keye": (causal_lm(keye, keye.KeyeConfig.tiny()),
        "e7f1483278b20170f7207a64a84339842724a53e758ffd5fb9e27d04a6110e51",
        "3a9bae88b62042e808384be45e93709c297703f71868f232185a45356fbbc436"),
    # made at PR 47, which brought the builder: held from here on
    "lfm2": (causal_lm(lfm2, lfm2.Lfm2Config.tiny()),
        "88e566ffafa22ccc31f0ec920db0f780e814a0cdb05d510ae2f60c8f94f3d2bc",
        "0afdfcecfbee5f872840d5dfd1052d5794bac36697610f3732278d5b219f7af5"),
    # made at PR 51, which brought the builder: held from here on
    "solar": (causal_lm(solar, solar.SolarConfig.tiny()),
        "e1628ecb4becbc7f830f4f1b978b3308eddff84ec494e688b049d964948e36c9",
        "0d3403ce321b51736f8128a4192ed4ab12507abe3aff3e90124c424c0e111198"),
}


@pytest.mark.parametrize("cell", list(_STEPS))
def test_the_cells_builders_trace_as_before(cell):
    """The tiny float32 train step of k = 2 (source lines cut) and the
    Programs as built (variables and ops with their attributes, in order of
    creation: the reference loads weights by name, the per-layer metrics read
    the scopes, XLA's cache keys the step by its HLO). BERT's, the
    sliding-window one's and the latent-expert hybrid's step digests came
    from `tests/test_ling.py::test_the_other_cells_builders_trace_as_before`
    unedited; kanana's and the hybrid's are also held where they were
    (`test_ling.py::test_latent_attention_as_kanana_calls_it_traces_as_
    before`, `test_nemotron3_super.py::test_without_a_latent_and_with_every_
    head_the_model_traces_as_before`)."""
    build, step, programs = _STEPS[cell]
    assert tiny_step_digests(build) == (step, programs)
