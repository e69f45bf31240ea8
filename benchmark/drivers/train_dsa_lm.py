"""Driver of a causal-LM training cell whose layers attend over a learned
selection of keys (an indexer scores the causal pairs, a query attends its
`topk` best): the program's own trainer (`models.keye`,
`fleet.distributed_optimizer`, `fluid.Executor.run_steps`) timed reading by
reading and held against the plain reference. Everything but the builder
call, the configuration's key names, what a reading fetches beside the
losses (each layer's indexer loss and count of selected keys, the first
layer's selection), two more numbers of the comparison and the faults of
`calibrate` is `drivers/train_lm.py`'s, imported, as in
`drivers/train_kda_lm.py`: `run` is `train_lm.run`'s code and `calibrate`
`train_gqa_lm.calibrate`'s over those modules' names, with this module's
`Trainer`, `check_readings`, `compare_lm` (and `faults`) in their place.

The three numbers. `index_loss_gap`: the largest relative gap of a layer's
indexer loss at either step. `select_mismatch_share`: of the (query, key)
choices the program and the reference made in the first layer at step 1,
the share only one of them made (near-ties at the `topk`-th score fall
either way under bf16 products; a wrong rule of selection moves most
choices). `moment1_dir_gap` (`train_kda_lm`'s): the first moments of the
reference's `vector_leaves`, the attention norms' scales, as vectors: a
gradient that leaks from the indexer's loss into its input turns them.
"""
from __future__ import annotations

import time
import types

import numpy as np

from .. import common
from . import train_gqa_lm, train_kda_lm, train_lm

# the configuration file's keys -> models.keye.KeyeConfig
_PUBLISHED = ("hidden_size", "num_attention_heads", "num_key_value_heads",
              "head_dim", "moe_intermediate_size", "num_experts_per_tok",
              "norm_topk_prob", "rms_norm_eps", "rope_theta")


class Trainer(train_lm.Trainer):
    """`train_lm.Trainer` with another builder and a longer fetch list: the
    one compiled step with its state that set-up builds, the check drives
    through its first steps and the window then times."""

    def __init__(self, cfg: dict, spec: dict, seed: int, chips: int):
        import jax
        import paddle_tpu as paddle
        import paddle_tpu.fluid as fluid
        from paddle_tpu.distributed import fleet
        from paddle_tpu.models import keye
        from paddle_tpu.testing import reset_programs

        if chips != 1:
            raise common.Refused("the causal-LM driver runs one chip's "
                                 "share on one chip")
        sa = cfg["sa_config"]
        if sa["indexer_num_kv_heads"] != 1:
            raise common.Refused("the indexer has one key head")
        self.cfg, self.spec, self.seed = cfg, spec, seed
        self.k = spec["steps_per_reading"]
        self.rows = spec["batch_per_chip"]
        self.seq = spec["seq"]
        self.ref = common.load_reference(cfg)
        self.model = keye
        reset_programs(seed=seed % (2 ** 31))
        mcfg = keye.KeyeConfig(
            vocab_size=cfg["vocab"], num_hidden_layers=cfg["layers"],
            num_experts=cfg["experts_total"],
            experts_held=cfg["num_experts"],
            expert_offset=cfg["expert_offset"], seq_len=self.seq,
            mrope_section=tuple(cfg["rope_scaling"]["mrope_section"]),
            indexer_num_heads=sa["indexer_num_heads"],
            indexer_head_dim=sa["indexer_head_dim"], index_topk=sa["topk"],
            indexer_norm_eps=cfg["assumed"]["indexer_norm_eps"],
            initializer_range=cfg["assumed"]["initializer_std"],
            **{key: cfg[key] for key in _PUBLISHED})
        _, self.loss, routed = keye.build_causal_lm_program(mcfg)
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
        # the losses, the first layer's routed choice and selection, every
        # layer's load, indexer loss and count of selected keys leave the
        # device in ONE run_steps call
        self.layers = len(routed)
        self.fetch = ([self.loss, routed[0][0], self.loss._selections[0]]
                      + [r[1] for r in routed]
                      + self.loss._auxiliary_losses
                      + self.loss._selected_pairs)
        self.exe = fluid.Executor()
        self.exe.run(fluid.default_startup_program())
        self.scope = fluid.global_scope()
        self.names = sorted(self.ref.param_shapes(cfg))
        # the benchmark's own weights, leaf by leaf on the device; the
        # reference starts from the same draws
        for name in self.names:
            if self.scope.find(name) is None:
                raise RuntimeError(f"the program has no parameter {name!r}")
            self.scope.set(name, self.fresh_leaf(name))

    def reading(self, feed) -> tuple:
        """One reading: `run_steps(k)` ending in the host read of its k
        losses, expert loads, indexer losses and counts of selected keys.
        -> (seconds to the call's return, seconds in all, CPU seconds the
        process used meanwhile, losses, the program's routing and selection
        gauges, (the routed choice and the selection still on the device,
        the indexer losses [k, layers]))"""
        n = self.layers
        t0, c0 = time.perf_counter(), time.process_time()
        out = self.exe.run_steps(self.k, feed=feed, fetch_list=self.fetch,
                                 return_numpy=False)
        t1 = time.perf_counter()
        losses = np.asarray(out[0], np.float64).reshape(-1)
        loads = np.stack([np.asarray(v) for v in out[3:3 + n]])  # [L, k, E]
        index = np.stack([np.asarray(v, np.float64).reshape(-1)
                          for v in out[3 + n:3 + 2 * n]], axis=1)   # [k, L]
        pairs = np.stack([np.asarray(v) for v in out[3 + 2 * n:]])
        t2 = time.perf_counter()
        gauges = self.model.record_expert_load(loads, self.rows * self.seq)
        gauges["selected_pairs_per_query"] = self.model.record_selection(
            pairs)
        return (t1 - t0, t2 - t0, time.process_time() - c0, losses, gauges,
                (out[1], out[2], index))

    def state_norms(self) -> dict:
        """The norms `train_lm.Trainer` takes, and Adam's first moment
        itself of the reference's `vector_leaves`."""
        return dict(super().state_norms(), moment1_vectors={
            n: np.asarray(self.scope.find(n + "_moment1_0"), np.float32)
            for n in self.ref.vector_leaves(self.cfg)})


def check_readings(tr: Trainer, feed) -> dict:
    """The check's numbers from the program: the first reading's losses and
    indexer losses, the routed choice and the selection of its first step,
    and the state after its k steps."""
    *_, losses, gauges, (top_idx, select, index) = tr.reading(feed)
    return {"losses": [float(v) for v in losses],
            "index_losses": index.tolist(),
            "first_route": np.asarray(top_idx)[0],
            "first_select": np.asarray(select[0]) != 0, "routing": gauges,
            **tr.state_norms()}


def select_mismatch_share(program_select, reference_select) -> float:
    """Of the (query, key) choices of the first layer at step 1 that the
    program and the reference made, the share only one of them made."""
    want = np.asarray(reference_select)
    got = np.asarray(program_select).reshape(want.shape)
    return float((got ^ want).sum() / (got.sum() + want.sum()))


def compare_lm(program: dict, reference: dict) -> dict:
    gaps = train_lm.compare_lm(program, reference)
    got = np.asarray(program["index_losses"])
    want = np.asarray(reference["index_losses"])
    gaps["index_loss_gap"] = float(np.max(np.abs(got - want)
                                          / np.abs(want)))
    gaps["select_mismatch_share"] = select_mismatch_share(
        program["first_select"], reference["first_select"])
    gaps["moment1_dir_gap"] = max(train_kda_lm.direction_gaps(
        program["moment1_vectors"], reference["moment1_vectors"]).values())
    return gaps


def _jsonable(result: dict) -> dict:
    return {k: v for k, v in train_lm._jsonable(result).items()
            if k not in ("first_select", "moment1_vectors")}


# `train_lm.run`'s code over that module's names, with this module's
# trainer, check, comparison and what of a result `checks.json` keeps
_OWN = {"Trainer": Trainer, "check_readings": check_readings,
        "compare_lm": compare_lm, "_jsonable": _jsonable}
run = types.FunctionType(train_lm.run.__code__, {**vars(train_lm), **_OWN},
                         "run")


def faults(cfg: dict, seq: int) -> dict:
    """The configuration with one thing wrong, for each fault the new
    mechanisms admit: what `correct` must not take for the model."""
    assumed, sa = cfg["assumed"], cfg["sa_config"]

    def fault(name):
        return dict(cfg, assumed=dict(assumed, fault=name))

    return {
        "selection_ignored": fault("selection_ignored"),
        "topk_quartered": dict(cfg, sa_config=dict(sa,
                                                   topk=sa["topk"] // 4)),
        "index_loss_left_out": fault("index_loss_left_out"),
        "index_input_attached": fault("index_input_attached"),
        "lowest_selected": fault("lowest_selected"),
    }


# `train_gqa_lm.calibrate`'s code (the sound gaps on every seed; on the
# control seeds a quarter of the row left out, the reference with each of
# `faults`, the fp8 control) over this module's names
calibrate = types.FunctionType(
    train_gqa_lm.calibrate.__code__,
    {**vars(train_gqa_lm), **_OWN, "faults": faults}, "calibrate")
