"""Driver of a causal-LM training cell whose layers differ by kind AND by
query-head count on the same KV heads, attend under an element-wise gate
with rotary positions on a part of a head, and feed forward densely or
through sigmoid-routed experts beside a shared one: the program's own
trainer (`models.laguna`, `fleet.distributed_optimizer`,
`fluid.Executor.run_steps`) timed reading by reading and held against the
plain reference. Everything but the builder call, the configuration's key
names and the faults of `calibrate` is imported, as in
`drivers/train_kda_gqa_lm.py`: `run` is `train_lm.run`'s code and
`calibrate` `train_gqa_lm.calibrate`'s over those modules' names, with this
module's `Trainer` and `faults` in their place; the comparison is
`train_kda_lm.compare_lm`: the losses, the per-leaf norms, the first routed
choice, and `moment1_dir_gap`, the first moments of the reference's
`vector_leaves` (every layer's `k_proj_w`) as VECTORS. A norm cannot see
where in a head the turned features lie: a turn is orthogonal, and with the
turned half at the wrong end a leaf's gradient keeps its size and changes
its direction.
"""
from __future__ import annotations

import types

from .. import common
from . import train_gqa_lm, train_kda_lm, train_lm

# the configuration file's keys -> models.laguna.LagunaConfig
_PUBLISHED = ("hidden_size", "intermediate_size", "num_hidden_layers",
              "num_key_value_heads", "head_dim", "sliding_window",
              "rope_parameters", "gating", "num_experts_per_tok",
              "moe_intermediate_size", "shared_expert_intermediate_size",
              "moe_routed_scaling_factor", "moe_apply_router_weight_on_input",
              "rms_norm_eps", "expert_offset", "first_layer")
_BY_LAYER = ("layer_types", "num_attention_heads_per_layer",
             "mlp_layer_types")


class Trainer(train_kda_lm.Trainer):
    """`train_lm.Trainer` with another builder (and `train_kda_lm`'s
    `state_norms`: the first moments of `vector_leaves` themselves): the
    one compiled step with its state that set-up builds, the check drives
    through its first steps and the window then times."""

    def __init__(self, cfg: dict, spec: dict, seed: int, chips: int):
        import jax
        import paddle_tpu as paddle
        import paddle_tpu.fluid as fluid
        from paddle_tpu.distributed import fleet
        from paddle_tpu.models import laguna
        from paddle_tpu.testing import reset_programs

        if chips != 1:
            raise common.Refused("the causal-LM driver runs one chip's "
                                 "share on one chip")
        self.cfg, self.spec, self.seed = cfg, spec, seed
        self.k = spec["steps_per_reading"]
        self.rows = spec["batch_per_chip"]
        self.seq = spec["seq"]
        self.ref = common.load_reference(cfg)
        self.model = laguna
        reset_programs(seed=seed % (2 ** 31))
        mcfg = laguna.LagunaConfig(
            vocab_size=cfg["vocab"], num_layers_held=cfg["layers"],
            num_experts=cfg["experts_total"],
            experts_held=cfg["num_experts"], seq_len=self.seq,
            initializer_range=cfg["assumed"]["initializer_std"],
            **{key: tuple(cfg[key]) for key in _BY_LAYER},
            **{key: cfg[key] for key in _PUBLISHED})
        _, self.loss, routed = laguna.build_causal_lm_program(mcfg)
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
        # reference starts from the same draws. The selection bias keeps
        # the startup program's zeros: it is no leaf of the reference
        for name in self.names:
            if self.scope.find(name) is None:
                raise RuntimeError(f"the program has no parameter {name!r}")
            self.scope.set(name, self.fresh_leaf(name))


# `train_lm.run`'s code over that module's names, with this module's
# trainer, `train_kda_lm`'s comparison and what of a result `checks.json`
# keeps
_OWN = {"Trainer": Trainer, "compare_lm": train_kda_lm.compare_lm,
        "_jsonable": train_kda_lm._jsonable}
run = types.FunctionType(train_lm.run.__code__, {**vars(train_lm), **_OWN},
                         "run")

FAULTS = ("gate_left_out", "full_rotary_all", "rotary_last_half",
          "full_grouped_by_8", "window_ignored", "scaling_1")


def faults(cfg: dict, seq: int) -> dict:
    """The configuration with one thing wrong, for each fault the new
    mechanisms admit: what `correct` must not take for the model
    (`reference/laguna_xs2.py` says what each name does)."""
    return {name: dict(cfg, assumed=dict(cfg["assumed"], fault=name))
            for name in FAULTS}


# `train_gqa_lm.calibrate`'s code (the sound gaps on every seed; on the
# control seeds a quarter of the row left out, the reference with each of
# `faults`, the fp8 control) over this module's names
calibrate = types.FunctionType(
    train_gqa_lm.calibrate.__code__,
    {**vars(train_gqa_lm), **_OWN, "faults": faults}, "calibrate")
