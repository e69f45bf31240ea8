"""Driver of a causal-LM training cell whose layers are each a state-space
mixer, an expert layer or attention: the program's own trainer
(`models.nemotron_h`, `fleet.distributed_optimizer`,
`fluid.Executor.run_steps`) timed reading by reading and held against the
plain reference. Everything but the builder call, the configuration's key
names and the faults of `calibrate` is `drivers/train_lm.py`'s, imported:
the feed, a reading, the state norms, the reference's blocks, the
comparison. `train_lm.run` names its own module's `Trainer`; `run` here is
that function's code over that module's names with this module's `Trainer`
in its place, not a third copy of its text (PERF.md section 7 names the
fold for the next `benchmark` PR).
"""
from __future__ import annotations

import types

from .. import common
from ..common import log
from . import train_lm
from .train_lm import (_quarter_left_out, _unload_programs, check_readings,
                       compare_lm, run_reference)

# the configuration file's keys -> models.nemotron_h.NemotronHConfig
_PUBLISHED = ("hidden_size", "hybrid_override_pattern",
              "num_attention_heads", "num_key_value_heads", "head_dim",
              "mamba_num_heads", "mamba_head_dim", "n_groups",
              "ssm_state_size", "conv_kernel", "chunk_size",
              "moe_intermediate_size", "moe_shared_expert_intermediate_size",
              "num_experts_per_tok", "routed_scaling_factor",
              "norm_topk_prob", "layer_norm_epsilon")


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
        reset_programs(seed=seed % (2 ** 31))
        mcfg = nemotron_h.NemotronHConfig(
            vocab_size=cfg["vocab"], num_hidden_layers=cfg["layers"],
            n_routed_experts=cfg["experts_total"],
            experts_held=cfg["n_routed_experts"],
            expert_offset=cfg["expert_offset"], seq_len=self.seq,
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


run = types.FunctionType(train_lm.run.__code__,
                         {**vars(train_lm), "Trainer": Trainer}, "run")


def faults(cfg: dict) -> dict:
    """The configuration with one thing wrong, for each fault the new
    mechanisms admit: what `correct` must not take for the model."""
    assumed = cfg["assumed"]
    return {
        "scan_states_bf16": dict(cfg, assumed=dict(
            assumed, scan_state_dtype="bfloat16")),
        "ssm_quarter_left_out": dict(cfg, assumed=dict(
            assumed, ssm_heads_kept=cfg["mamba_num_heads"] * 3 // 4)),
        "float32_parts_in_bf16": dict(cfg, assumed=dict(
            assumed, float32_parts="bfloat16")),
    }


def calibrate(cell, seeds, control_seeds):
    """The readings the limits are set from, at the cell's own size: the
    sound program's gaps on every seed of `seeds`, and on `control_seeds`
    what each fault the limits are there for would read: the fp8 control, a
    quarter of the row left out, and the reference with each of `faults`
    against the sound reference. One process: the trainer is rebuilt per
    seed, its executable comes from the cache."""
    import jax
    cfg, spec = cell["config_file"], cell["traffic_file"]
    chips = min(cell["chips"], len(jax.devices()))
    rows = []
    for seed in seeds:
        tr = Trainer(cfg, spec, seed, chips)
        feed, host = tr.device_feed(0)
        program = check_readings(tr, feed)
        tr.free()
        reference = run_reference(tr, host)
        row = {"seed": seed, "program": compare_lm(program, reference),
               "routing": program["routing"]}
        if seed in control_seeds:
            row["fault_quarter_batch_loss_gap"] = _quarter_left_out(
                tr, host, reference)
            for name, wrong in faults(cfg).items():
                # another program of the reference's size: two do not fit
                _unload_programs()
                row["fault_" + name] = compare_lm(
                    run_reference(tr, host, cfg=wrong), reference)
            _unload_programs()
            row["control_fp8"] = compare_lm(
                run_reference(tr, host, "fp8"), reference)
        log(f"calibrate {cell['name']} {row}")
        rows.append(row)
        del tr
    return rows
