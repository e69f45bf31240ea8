"""Driver of a causal-LM training cell whose layers attend by the gated
delta rule or by latent attention, over a chip's share of the heads and of
the experts: the program's own trainer (`models.ling`,
`fleet.distributed_optimizer`, `fluid.Executor.run_steps`) timed reading by
reading and held against the plain reference. Everything but the builder
call, the configuration's key names, the faults of `calibrate` and one more
number of the comparison (`moment1_dir_gap`: first moments as vectors) is
`drivers/train_lm.py`'s, imported, as in `drivers/train_hybrid_lm.py`: `run`
is `train_lm.run`'s code, and `calibrate` `train_hybrid_lm.calibrate`'s,
over those modules' names with this module's `Trainer`, `compare_lm` (and
`faults`) in their place.
"""
from __future__ import annotations

import types

import numpy as np

from .. import common
from . import train_hybrid_lm, train_lm

# the configuration file's keys -> models.ling.LingConfig
_PUBLISHED = ("hidden_size", "num_hidden_layers", "layer_group_size",
              "first_k_dense_replace", "head_dim", "qk_nope_head_dim",
              "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
              "short_conv_kernel_size", "kda_lower_bound", "kda_chunk_size",
              "intermediate_size", "moe_intermediate_size",
              "moe_shared_expert_intermediate_size", "num_experts_per_tok",
              "n_group", "topk_group", "routed_scaling_factor",
              "norm_topk_prob", "rms_norm_eps", "rope_theta", "expert_offset",
              "first_layer")


class Trainer(train_lm.Trainer):
    """`train_lm.Trainer` with another builder: the one compiled step with
    its state that set-up builds, the check drives through its first steps
    and the window then times."""

    def __init__(self, cfg: dict, spec: dict, seed: int, chips: int):
        import jax
        import paddle_tpu as paddle
        import paddle_tpu.fluid as fluid
        from paddle_tpu.distributed import fleet
        from paddle_tpu.models import ling
        from paddle_tpu.testing import reset_programs

        if chips != 1:
            raise common.Refused("the causal-LM driver runs one chip's "
                                 "share on one chip")
        if cfg["rotary_dim"] != cfg["qk_rope_head_dim"]:
            raise common.Refused("the latent layers turn `qk_rope_head_dim` "
                                 "features; `rotary_dim` says another count")
        self.cfg, self.spec, self.seed = cfg, spec, seed
        self.k = spec["steps_per_reading"]
        self.rows = spec["batch_per_chip"]
        self.seq = spec["seq"]
        self.ref = common.load_reference(cfg)
        self.model = ling
        reset_programs(seed=seed % (2 ** 31))
        mcfg = ling.LingConfig(
            vocab_size=cfg["vocab"], num_layers_held=cfg["layers"],
            num_experts=cfg["experts_total"],
            experts_held=cfg["num_experts"],
            num_attention_heads=cfg["heads_total"],
            heads_held=cfg["num_attention_heads"], seq_len=self.seq,
            initializer_range=cfg["assumed"]["initializer_std"],
            expert_swiglu_limit_list=tuple(
                cfg.get("expert_swiglu_limit_list", ())),
            share_expert_swiglu_limit_list=tuple(
                cfg.get("share_expert_swiglu_limit_list", ())),
            **{key: cfg[key] for key in _PUBLISHED})
        _, self.loss, routed = ling.build_causal_lm_program(mcfg)
        fleet.init(is_collective=True)
        strategy = fleet.DistributedStrategy()
        strategy.amp = True
        if cfg["assumed"].get("recompute"):
            strategy.recompute = True
            strategy.recompute_configs = {
                "checkpoints": list(self.loss._layer_checkpoints)}
        fleet.distributed_optimizer(
            paddle.optimizer.Adam(learning_rate=self.ref.ADAM["lr"]),
            strategy).minimize(self.loss)
        if len(jax.devices()) > chips:
            # a host with more chips than the cell asks for: the same
            # program on a mesh cut to the cell's one chip
            from paddle_tpu.parallel import DistConfig, attach, build_mesh
            prog = fluid.default_main_program()
            attach(prog, DistConfig(
                mesh=build_mesh(dp=chips, devices=jax.devices()[:chips]),
                param_rules=prog._dist_config.param_rules))
        # the losses, the first expert layer's routed choice and every
        # expert layer's load leave the device in ONE run_steps call
        self.fetch = [self.loss, routed[0][0]] + [r[1] for r in routed]
        self.exe = fluid.Executor()
        self.exe.run(fluid.default_startup_program())
        self.scope = fluid.global_scope()
        self.names = sorted(self.ref.param_shapes(cfg))
        # the benchmark's own weights, leaf by leaf on the device; the
        # reference starts from the same draws
        for name in self.names + sorted(self.ref.buffer_shapes(cfg)):
            if self.scope.find(name) is None:
                raise RuntimeError(f"the program has no parameter {name!r}")
            self.scope.set(name, self.fresh_leaf(name))

    def state_norms(self) -> dict:
        """The norms `train_lm.Trainer` takes, and Adam's first moment
        itself of the reference's `vector_leaves`."""
        return dict(super().state_norms(), moment1_vectors={
            n: np.asarray(self.scope.find(n + "_moment1_0"), np.float32)
            for n in self.ref.vector_leaves(self.cfg)})


def direction_gaps(got: dict, want: dict) -> dict:
    """Each leaf's |got - want| / |want|, the leaves taken as vectors: what
    a norm cannot see of a rounding that is right on average."""
    return {n: float(np.linalg.norm(got[n] - want[n])
                     / np.linalg.norm(want[n])) for n in want}


def compare_lm(program: dict, reference: dict) -> dict:
    gaps = train_lm.compare_lm(program, reference)
    leaves = direction_gaps(program["moment1_vectors"],
                            reference["moment1_vectors"])
    common.log("moment1 direction gaps "
               + " ".join(f"{n}={v:.4f}" for n, v in sorted(leaves.items())))
    gaps["moment1_dir_gap"] = max(leaves.values())
    return gaps


def _jsonable(result: dict) -> dict:
    return {k: v for k, v in train_lm._jsonable(result).items()
            if k != "moment1_vectors"}


# `train_lm.run`'s code over that module's names, with this module's
# trainer, comparison and what of a result `checks.json` keeps
_OWN = {"Trainer": Trainer, "compare_lm": compare_lm, "_jsonable": _jsonable}
run = types.FunctionType(train_lm.run.__code__, {**vars(train_lm), **_OWN},
                         "run")


def faults(cfg: dict) -> dict:
    """The configuration with one thing wrong, for each fault the new
    mechanisms admit: what `correct` must not take for the model. (It does
    take `kda_state_bf16`: a step under AMP moves every number by more than
    that rounding does, PERF.md section 6, PR 36.)"""
    assumed = cfg["assumed"]
    return {
        "kda_state_bf16": dict(cfg, assumed=dict(
            assumed, kda_state_dtype="bfloat16")),
        "kda_no_delta": dict(cfg, assumed=dict(assumed, kda_no_delta=True)),
        "no_group_limit": dict(cfg, assumed=dict(
            assumed, no_group_limit=True)),
        "kda_quarter_left_out": dict(cfg, assumed=dict(
            assumed, kda_heads_kept=cfg["num_attention_heads"] * 3 // 4)),
    }


# `train_hybrid_lm.calibrate`'s code (the sound gaps on every seed; on the
# control seeds a quarter of the row left out, the reference with each of
# `faults`, the fp8 control) over this module's `Trainer` and `faults`
calibrate = types.FunctionType(
    train_hybrid_lm.calibrate.__code__,
    {**vars(train_hybrid_lm), **_OWN, "faults": faults}, "calibrate")
