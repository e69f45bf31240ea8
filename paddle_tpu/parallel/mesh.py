"""Device mesh management: the TPU-native replacement for NCCL rings.

Reference counterpart: platform/collective_helper.h:50-69 (ring_id-keyed NCCL
comm registry), c_gen_nccl_id/c_comm_init bootstrap ops, RoleMaker env contract
(fleet/base/role_maker.py:673-737). TPU-native: topology comes from the XLA
runtime; "rings" become named mesh axes (dp/tp/pp/sp/ep); bootstrap for
multi-host is jax.distributed.initialize (DCN), after which every host sees the
global device list. There is no id exchange, no comm streams, no sync ops —
XLA schedules collectives.
"""
from __future__ import annotations

import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

P = PartitionSpec

_current_mesh: Optional[Mesh] = None


def init_parallel_env(coordinator_address: Optional[str] = None,
                      num_processes: Optional[int] = None,
                      process_id: Optional[int] = None):
    """Multi-host bootstrap (reference init_parallel_env distributed/parallel.py:46
    + c_gen_nccl_id gRPC exchange). On TPU pods jax.distributed discovers peers
    from the TPU metadata; env vars PADDLE_TRAINER_ID/PADDLE_TRAINER_ENDPOINTS
    are honored for parity with the reference's contract."""
    if jax.process_count() > 1:
        return  # already initialized
    endpoints = os.environ.get("PADDLE_TRAINER_ENDPOINTS", "")
    trainer_id = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    if coordinator_address is None and endpoints:
        coordinator_address = endpoints.split(",")[0]
        num_processes = len(endpoints.split(","))
        process_id = trainer_id
    if coordinator_address and (num_processes or 0) > 1:
        jax.distributed.initialize(coordinator_address=coordinator_address,
                                   num_processes=num_processes,
                                   process_id=process_id)


def get_rank() -> int:
    return jax.process_index()


def get_world_size() -> int:
    return jax.process_count()


def build_mesh(dp: int = -1, tp: int = 1, pp: int = 1, sp: int = 1,
               ep: int = 1, devices=None) -> Mesh:
    """Create a named mesh over all devices. dp=-1 means 'use the rest'.

    Axis names are the paddle_tpu convention used by every sharding rule:
      dp — data parallel   tp — tensor/model parallel
      pp — pipeline        sp — sequence/context parallel
      ep — expert parallel (MoE)
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    fixed = tp * pp * sp * ep
    if dp == -1:
        assert n % fixed == 0, f"{n} devices not divisible by tp*pp*sp*ep={fixed}"
        dp = n // fixed
    assert dp * fixed == n, (
        f"mesh {dp}x{tp}x{pp}x{sp}x{ep} != {n} devices")
    arr = np.array(devices).reshape(dp, tp, pp, sp, ep)
    return Mesh(arr, axis_names=("dp", "tp", "pp", "sp", "ep"))


def set_mesh(mesh: Mesh):
    global _current_mesh
    _current_mesh = mesh


def get_mesh() -> Optional[Mesh]:
    return _current_mesh


def default_mesh() -> Mesh:
    global _current_mesh
    if _current_mesh is None:
        _current_mesh = build_mesh()
    return _current_mesh


def named_sharding(mesh: Mesh, spec: PartitionSpec) -> NamedSharding:
    return NamedSharding(mesh, spec)


def data_sharding(mesh: Mesh, ndim: int, batch_axes=("dp",)) -> NamedSharding:
    """Shard dim 0 over the data axes, replicate the rest."""
    spec = [None] * ndim
    if ndim > 0:
        spec[0] = batch_axes if len(batch_axes) > 1 else batch_axes[0]
    return NamedSharding(mesh, P(*spec))


class ShardingRules:
    """Name-pattern -> PartitionSpec table for parameters (the TP story).

    The reference has no TP (SURVEY §2.8: ABSENT); this is the beyond-parity
    capability: Megatron-style sharding expressed as data, applied by the
    Executor/pjit path. Patterns are checked in order; first regex match wins.
    """

    def __init__(self, rules: Sequence[Tuple[str, PartitionSpec]] = (),
                 default: PartitionSpec = P()):
        import re
        self._rules = [(re.compile(pat), spec) for pat, spec in rules]
        self._default = default

    def spec_for(self, name: str, shape=None) -> PartitionSpec:
        # [L]-stacked per-layer params (apply_layer_scan,
        # parallel/transforms.py): the per-layer rule applies shifted one
        # dim right — the stacked layer axis stays unsharded
        if name.endswith("@LAYERS"):
            base = self.spec_for(name[:-len("@LAYERS")],
                                 tuple(shape[1:]) if shape else None)
            return P(None, *base)
        for pat, spec in self._rules:
            if pat.search(name):
                return spec
        return self._default

    def sharding_for(self, mesh: Mesh, name: str, shape=None) -> NamedSharding:
        spec = self.spec_for(name, shape)
        if shape is not None:
            # drop axes that don't divide the dim (XLA requires even shards)
            fixed = []
            for dim, ax in zip(shape, list(spec) + [None] * (len(shape) - len(spec))):
                if ax is None:
                    fixed.append(None)
                    continue
                size = mesh.shape[ax] if isinstance(ax, str) else int(
                    np.prod([mesh.shape[a] for a in ax]))
                fixed.append(ax if dim % size == 0 and dim > 0 else None)
            spec = P(*fixed)
        return NamedSharding(mesh, spec)


REPLICATED = ShardingRules()


def moe_sharding_rules(extra=()) -> "ShardingRules":
    """Expert-parallel rules: shard the leading [E] dim of switch_moe and
    routed_moe expert weights over the mesh's ep axis (ops/moe.py) — GSPMD then lowers the
    dispatch einsum to an all-to-all over ICI."""
    rules = [(r"_expert_(w|b)[12]_?\d*$", P("ep")),
             # routed_moe's gated experts (models/deepseek_v3.py)
             (r"_experts_(gate|up|down)_w$", P("ep"))]
    return ShardingRules(list(extra) + rules)


def transformer_tp_rules(extra=()) -> "ShardingRules":
    """The Megatron marker -> PartitionSpec table shared by every
    transformer in models/ (bert.py / gpt.py use the same param-name
    markers): column-parallel QKV & FFN-in (shard the output dim over tp),
    row-parallel attn-proj & FFN-out (shard the input dim). Models append
    only their embedding/head rules via `extra`."""
    rules = [
        (r"_attn_qkv_w$", P(None, "tp")),
        (r"_attn_qkv_b$", P("tp")),
        (r"_ffn_in_w$", P(None, "tp")),
        (r"_ffn_in_b$", P("tp")),
        (r"_attn_proj_w$", P("tp", None)),
        (r"_ffn_out_w$", P("tp", None)),
    ]
    return moe_sharding_rules(extra=list(extra) + rules)
