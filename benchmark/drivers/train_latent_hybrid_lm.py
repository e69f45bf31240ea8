"""Driver of a causal-LM training cell of the Nemotron-H family whose
experts live in a latent and whose mixers hold one chip's share of a layer's
heads: the program's own trainer (`models.nemotron_h` with
`moe_latent_size` and the held heads, `fleet.distributed_optimizer`,
`fluid.Executor.run_steps`) timed reading by reading and held against the
plain reference. Everything but the builder call, the configuration's key
names, the faults of `calibrate`, a route of another width in the comparison
and the log of what the step's trace lowered is `drivers/train_lm.py`'s,
imported, as in `drivers/train_kda_lm.py`: `run` is `train_lm.run`'s code,
and `calibrate` `train_hybrid_lm.calibrate`'s, over those modules' names
with this module's `Trainer`, `check_readings`, `compare_lm` (and `faults`)
in their place.
"""
from __future__ import annotations

import types

import numpy as np

from .. import common
from . import train_hybrid_lm, train_lm
from .train import compare

# the configuration file's keys -> models.nemotron_h.NemotronHConfig, beside
# the five whose top-level number is what is HELD (`Trainer.__init__`)
_PUBLISHED = ("hidden_size", "hybrid_override_pattern", "head_dim",
              "mamba_head_dim", "ssm_state_size", "conv_kernel", "chunk_size",
              "moe_intermediate_size", "moe_shared_expert_intermediate_size",
              "moe_latent_size", "num_experts_per_tok",
              "routed_scaling_factor", "norm_topk_prob", "layer_norm_epsilon",
              "expert_offset")

# what one trace of the step lowered, by the program's own counters
_LOWERED = ("moe.layers_lowered", "moe.latent_layers_lowered",
            "moe.rows_bounded", "moe.bwd_residual", "moe.bwd_recomputed",
            "moe.grouped_pallas", "moe.grouped_xla", "ssm.scan_pallas",
            "ssm.scan_xla", "ssm.bwd_residual", "ssm.bwd_recomputed",
            "attention.flash_full", "attention.flash_kv_grouped")


def _counters() -> dict:
    from paddle_tpu.observability import metrics
    return {name: metrics.get(name) for name in _LOWERED}


class Trainer(train_lm.Trainer):
    """`train_lm.Trainer` with another builder: the one compiled step with
    its state that set-up builds, the check drives through its first steps
    and the window then times."""

    def __init__(self, cfg: dict, spec: dict, seed: int, chips: int):
        import jax
        import paddle_tpu as paddle
        import paddle_tpu.fluid as fluid
        from paddle_tpu.distributed import fleet
        from paddle_tpu.models import nemotron_h
        from paddle_tpu.testing import reset_programs

        if chips != 1:
            raise common.Refused("the causal-LM driver runs one chip's "
                                 "share on one chip")
        self.cfg, self.spec, self.seed = cfg, spec, seed
        self.k = spec["steps_per_reading"]
        self.rows = spec["batch_per_chip"]
        self.seq = spec["seq"]
        self.ref = common.load_reference(cfg)
        self.model = nemotron_h
        self.lowered_before = _counters()
        reset_programs(seed=seed % (2 ** 31))
        mcfg = nemotron_h.NemotronHConfig(
            vocab_size=cfg["vocab"], num_hidden_layers=cfg["layers"],
            n_routed_experts=cfg["experts_total"],
            experts_held=cfg["n_routed_experts"],
            mamba_num_heads=cfg["mamba_heads_total"],
            mamba_heads_held=cfg["mamba_num_heads"],
            n_groups=cfg["mamba_groups_total"],
            mamba_groups_held=cfg["n_groups"],
            num_attention_heads=cfg["heads_total"],
            heads_held=cfg["num_attention_heads"],
            num_key_value_heads=cfg["kv_heads_total"],
            kv_heads_held=cfg["num_key_value_heads"], seq_len=self.seq,
            initializer_range=cfg["assumed"]["initializer_std"],
            **{key: cfg[key] for key in _PUBLISHED})
        _, self.loss, routed = nemotron_h.build_causal_lm_program(mcfg)
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


def check_readings(tr: Trainer, feed) -> dict:
    """`train_lm.check_readings`, and what the first reading's one trace of
    the step lowered: the routed op's layers in a latent and on a bounded
    buffer, its backward by the rule or under a recomputed segment, the
    grouped matmuls' and the selective scan's routes (the counters of
    `docs/observability.md`, as they rose since the trainer was built)."""
    program = train_lm.check_readings(tr, feed)
    program["lowered"] = {name: int(now - tr.lowered_before[name])
                          for name, now in _counters().items()}
    common.log("the step's trace lowered " + " ".join(
        f"{name}=+{rise}" for name, rise in program["lowered"].items()))
    return program


def compare_lm(program: dict, reference: dict) -> dict:
    """`train_lm.compare_lm`; a routed choice of another width than the
    reference's (a fault of `calibrate`: 6 slots a token for 22) differs in
    every token's set."""
    got, want = (np.asarray(r["first_route"]) for r in (program, reference))
    if got.shape[-1] == want.shape[-1]:
        return train_lm.compare_lm(program, reference)
    return dict(compare(program, reference), route_mismatch_share=1.0)


# `train_lm.run`'s code over that module's names, with this module's
# trainer, first reading and comparison
_OWN = {"Trainer": Trainer, "check_readings": check_readings,
        "compare_lm": compare_lm}
run = types.FunctionType(train_lm.run.__code__, {**vars(train_lm), **_OWN},
                         "run")


def faults(cfg: dict) -> dict:
    """The configuration with one thing wrong, for each fault the new
    mechanisms admit: what `correct` must not take for the model. The
    routed part is left out of the third of the five expert layers. (It
    does take `scan_states_float8` on the chip: an unbiased rounding of the
    state moves a leaf's norm by the rounding's square, 0.006 where the
    sound step reads up to 0.009; PERF.md sections 6 and 7, PR 39. At the
    rehearsal's 32 tokens it fails.)"""
    assumed = cfg["assumed"]
    experts = [n for n, kind in enumerate(
        cfg["hybrid_override_pattern"][:cfg["layers"]]) if kind == "E"]
    return {
        "top6_for_top22": dict(cfg, num_experts_per_tok=6),
        "weights_without_factor": dict(cfg, routed_scaling_factor=1.0),
        "one_routed_part_left_out": dict(cfg, assumed=dict(
            assumed, routed_left_out=f"l{experts[len(experts) // 2]}_")),
        "scan_states_float8": dict(cfg, assumed=dict(
            assumed, scan_state_dtype="float8_e4m3fn")),
    }


# `train_hybrid_lm.calibrate`'s code (the sound gaps on every seed; on the
# control seeds a quarter of the row left out, the reference with each of
# `faults`, the fp8 control) over this module's names
calibrate = types.FunctionType(
    train_hybrid_lm.calibrate.__code__,
    {**vars(train_hybrid_lm), **_OWN, "faults": faults}, "calibrate")
