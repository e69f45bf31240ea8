"""Driver of a causal-LM training cell most of whose layers mix along the
sequence by a gated short convolution, the others by attention on grouped KV
heads normed a head, under a tied head: the program's own trainer
(`models.lfm2`, `fleet.distributed_optimizer`, `fluid.Executor.run_steps`)
timed reading by reading and held against the plain reference. Everything
but the builder call, the configuration's key names and the faults of
`calibrate` is imported, as in `drivers/train_dsa_lm.py`: `run` is
`train_lm.run`'s code and `calibrate` `train_gqa_lm.calibrate`'s over those
modules' names, with this module's `Trainer` (and `faults`) and
`train_kda_lm`'s comparison in their place: the losses, the per-leaf norms,
the first routed choice, and `moment1_dir_gap`, the first moments of the
reference's `vector_leaves` (taps, per-head norm scales, the last norm's
scale) as vectors.
"""
from __future__ import annotations

import types

import numpy as np

from .. import common
from . import train_gqa_lm, train_kda_lm, train_lm
from .train_kda_lm import _jsonable, compare_lm

# the configuration file's keys -> models.lfm2.Lfm2Config
_PUBLISHED = ("hidden_size", "num_hidden_layers", "conv_L_cache",
              "num_attention_heads", "num_key_value_heads", "head_dim",
              "num_dense_layers", "intermediate_size",
              "moe_intermediate_size", "num_experts_per_tok",
              "norm_topk_prob", "routed_scaling_factor", "use_expert_bias",
              "norm_eps", "expert_offset", "first_layer")


class Trainer(train_lm.Trainer):
    """`train_lm.Trainer` with another builder: the one compiled step with
    its state that set-up builds, the check drives through its first steps
    and the window then times."""

    def __init__(self, cfg: dict, spec: dict, seed: int, chips: int):
        import jax
        import paddle_tpu as paddle
        import paddle_tpu.fluid as fluid
        from paddle_tpu.distributed import fleet
        from paddle_tpu.models import lfm2
        from paddle_tpu.testing import reset_programs

        if chips != 1:
            raise common.Refused("the causal-LM driver runs one chip's "
                                 "share on one chip")
        rope = cfg["rope_parameters"]
        if rope["rope_type"] != "default" or cfg["conv_bias"]:
            raise common.Refused("the builder turns by the default rule and "
                                 "its convolution has no bias")
        self.cfg, self.spec, self.seed = cfg, spec, seed
        self.k = spec["steps_per_reading"]
        self.rows = spec["batch_per_chip"]
        self.seq = spec["seq"]
        self.ref = common.load_reference(cfg)
        self.model = lfm2
        reset_programs(seed=seed % (2 ** 31))
        mcfg = lfm2.Lfm2Config(
            vocab_size=cfg["vocab"], num_layers_held=cfg["layers"],
            num_experts=cfg["experts_total"],
            experts_held=cfg["num_experts"],
            layer_types=tuple(cfg["layer_types"]),
            rope_theta=rope["rope_theta"], seq_len=self.seq,
            initializer_range=cfg["assumed"]["initializer_std"],
            **{key: cfg[key] for key in _PUBLISHED})
        _, self.loss, routed = lfm2.build_causal_lm_program(mcfg)
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


# `train_lm.run`'s code over that module's names, with this module's
# trainer, `train_kda_lm`'s comparison and what of a result `checks.json`
# keeps
_OWN = {"Trainer": Trainer, "compare_lm": compare_lm, "_jsonable": _jsonable}
run = types.FunctionType(train_lm.run.__code__, {**vars(train_lm), **_OWN},
                         "run")


def faults(cfg: dict, seq: int) -> dict:
    """The configuration with one thing wrong, for each fault the new
    mechanisms admit: what `correct` must not take for the model."""
    return {name: dict(cfg, assumed=dict(cfg["assumed"], fault=name))
            for name in ("gate_left_out", "taps_reversed",
                         "qk_norm_left_out", "head_untied")}


# `train_gqa_lm.calibrate`'s code (the sound gaps on every seed; on the
# control seeds a quarter of the row left out, the reference with each of
# `faults`, the fp8 control) over this module's names
calibrate = types.FunctionType(
    train_gqa_lm.calibrate.__code__,
    {**vars(train_gqa_lm), **_OWN, "faults": faults}, "calibrate")
