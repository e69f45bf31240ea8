"""Driver of a causal-LM training cell whose layers attend by the gated
delta rule with an unbounded decay or by gated softmax attention on grouped
KV heads, every layer sparse, over a chip's share of the heads and of the
experts: the program's own trainer (`models.solar`,
`fleet.distributed_optimizer`, `fluid.Executor.run_steps`) timed reading by
reading and held against the plain reference. Everything but the builder
call, the configuration's key names and the faults of `calibrate` is
imported, as in `drivers/train_conv_lm.py`: `run` is `train_lm.run`'s code
and `calibrate` `train_gqa_lm.calibrate`'s over those modules' names, with
this module's `Trainer` (and `faults`) and `train_kda_lm`'s comparison in
their place: the losses, the per-leaf norms, the first routed choice, and
`moment1_dir_gap`, the first moments of the reference's `vector_leaves` as
vectors. The comparison also puts the least log decay of the first
reading's first step into the gauge `kda.min_log_decay`: the reference's
reading on the program's weights and tokens (under recomputation g lies
inside a segment and no fetch reaches it), kept in `checks.json`.

The timed window keeps the configuration's own mild draws, under which the
sibling's bounded form of the scan would give the same answers. `calibrate`
therefore adds the control `steep_decay` on its control seeds: program and
reference with `dt_bias` drawn from steps of `STEEP_DT`, the spread a
trained gate has: most channels as mild as the timed draws, about one in
seven below -88 / 16 a token. There the program as built must be `correct`
by the cell's limits, and the program with the sibling's form of the scan
in its place must not be.
"""
from __future__ import annotations

import types

from .. import common
from . import train_gqa_lm, train_kda_lm, train_lm
from .train_kda_lm import _jsonable

# the configuration file's keys -> models.solar.SolarConfig
_PUBLISHED = ("hidden_size", "num_hidden_layers", "head_dim", "use_rope",
              "use_gqa_gate", "kda_use_full_proj", "kda_allow_neg_eigval",
              "kda_chunk_size", "first_k_dense_replace", "intermediate_size",
              "moe_intermediate_size", "n_shared_experts",
              "num_experts_per_tok", "norm_topk_prob",
              "routed_scaling_factor", "rms_norm_eps", "expert_offset",
              "first_layer")

# the control `steep_decay`: `dt_bias` the inverse softplus of a log-uniform
# step in this range, the configuration's own floor and forty times its
# ceiling (g = -exp(A_log) softplus(.), exp(A_log) in (1, 16): g from -0.001
# to -64 a token at the start), and the bound the sibling's builder hands
# `kda_scan`, which picks the form around the blocks' sums
STEEP_DT = (0.001, 4.0)
SIBLING_BOUND = -5.0


class Trainer(train_kda_lm.Trainer):
    """`train_lm.Trainer` with another builder (and `train_kda_lm`'s
    `state_norms`: the first moments of `vector_leaves` themselves): the
    one compiled step with its state that set-up builds, the check drives
    through its first steps and the window then times."""

    def __init__(self, cfg: dict, spec: dict, seed: int, chips: int,
                 scan_bound=None):
        import jax
        import paddle_tpu as paddle
        import paddle_tpu.fluid as fluid
        from paddle_tpu.distributed import fleet
        from paddle_tpu.models import solar
        from paddle_tpu.testing import reset_programs

        if chips != 1:
            raise common.Refused("the causal-LM driver runs one chip's "
                                 "share on one chip")
        lin = cfg["linear_attn_config"]
        if lin["num_kv_heads"] is not None:
            raise common.Refused("the builder's delta rule has a k and a v "
                                 "a head; `num_kv_heads` says another count")
        self.cfg, self.spec, self.seed = cfg, spec, seed
        self.k = spec["steps_per_reading"]
        self.rows = spec["batch_per_chip"]
        self.seq = spec["seq"]
        self.ref = common.load_reference(cfg)
        self.model = solar
        reset_programs(seed=seed % (2 ** 31))
        mcfg = solar.SolarConfig(
            vocab_size=cfg["vocab"], num_layers_held=cfg["layers"],
            gqa_layers=tuple(cfg["gqa_layers"]),
            n_routed_experts=cfg["experts_total"],
            experts_held=cfg["n_routed_experts"],
            num_attention_heads=cfg["heads_total"],
            heads_held=cfg["num_attention_heads"],
            num_key_value_heads=cfg["kv_heads_total"],
            kv_heads_held=cfg["num_key_value_heads"],
            linear_num_heads=cfg["linear_heads_total"],
            linear_heads_held=lin["num_heads"],
            linear_head_dim=lin["head_dim"],
            short_conv_kernel_size=lin["short_conv_kernel_size"],
            seq_len=self.seq,
            initializer_range=cfg["assumed"]["initializer_std"],
            **{key: cfg[key] for key in _PUBLISHED})
        _, self.loss, routed = solar.build_causal_lm_program(mcfg)
        if scan_bound is not None:
            # the control's fault, in the PROGRAM: the scan told of a bound
            # its gate does not keep
            for op in fluid.default_main_program().global_block().ops:
                if op.type == "kda_scan":
                    op.attrs["lower_bound"] = scan_bound
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
        # the losses, the first layer's routed choice and every layer's
        # expert load leave the device in ONE run_steps call
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


def compare_lm(program: dict, reference: dict) -> dict:
    """`train_kda_lm.compare_lm`, and the gauge `kda.min_log_decay`."""
    from paddle_tpu.observability import metrics
    metrics.set_gauge("kda.min_log_decay", reference["min_log_decay"])
    return train_kda_lm.compare_lm(program, reference)


# `train_lm.run`'s code over that module's names, with this module's
# trainer and comparison and what of a result `checks.json` keeps
_OWN = {"Trainer": Trainer, "compare_lm": compare_lm, "_jsonable": _jsonable}
run = types.FunctionType(train_lm.run.__code__, {**vars(train_lm), **_OWN},
                         "run")


def faults(cfg: dict, seq: int) -> dict:
    """The configuration with one thing wrong, for each fault the new
    mechanisms admit: what `correct` must not take for the model."""
    return {name: dict(cfg, assumed=dict(cfg["assumed"], fault=name))
            for name in ("beta_unscaled", "bounded_gate",
                         "attn_gate_left_out")}


def steep_decay(cell, seed: int) -> dict:
    """The control for what the timed draws never reach: program and
    reference from `dt_bias` drawn over `STEEP_DT`. -> the program's gaps
    to the reference (`exact`: must hold the cell's limits), the same
    program with `kda_scan` handed the sibling's bound (`bounded_scan`:
    must not; an overflow reads nan, which no limit holds), and the
    reference's least log decay."""
    import jax
    spec = cell["traffic_file"]
    cfg = dict(cell["config_file"], assumed=dict(
        cell["config_file"]["assumed"], dt_range=list(STEEP_DT)))
    chips = min(cell["chips"], len(jax.devices()))
    train_lm._unload_programs()
    tr = Trainer(cfg, spec, seed, chips)
    feed, host = tr.device_feed(0)
    program = train_lm.check_readings(tr, feed)
    tr.free()
    reference = train_lm.run_reference(tr, host)
    row = {"min_log_decay": reference["min_log_decay"],
           "exact": compare_lm(program, reference)}
    train_lm._unload_programs()
    tr = Trainer(cfg, spec, seed, chips, scan_bound=SIBLING_BOUND)
    program = train_lm.check_readings(tr, tr.device_feed(0)[0])
    tr.free()
    row["bounded_scan"] = compare_lm(program, reference)
    return row


# `train_gqa_lm.calibrate`'s code (the sound gaps on every seed; on the
# control seeds a quarter of the row left out, the reference with each of
# `faults`, the fp8 control) over this module's names
_calibrate = types.FunctionType(
    train_gqa_lm.calibrate.__code__,
    {**vars(train_gqa_lm), **_OWN, "faults": faults}, "calibrate")


def calibrate(cell, seeds, control_seeds):
    """`_calibrate`'s rows, the control seeds' with `steep_decay`."""
    rows = _calibrate(cell, seeds, control_seeds)
    for row in rows:
        if row["seed"] in control_seeds:
            row["steep_decay"] = steep_decay(cell, row["seed"])
            common.log(f"calibrate {cell['name']} steep_decay "
                       f"{row['seed']}: {row['steep_decay']}")
    return rows
