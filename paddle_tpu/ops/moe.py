"""Mixture-of-Experts with expert parallelism (beyond-reference, SURVEY
§2.8: TP/SP/EP are ABSENT in the reference — this makes the fleet
`expert_parallel_degree` knob real).

TPU-native design (the Switch-Transformer / Mesh-TF dispatch pattern): a
top-1/top-2 gated expert FFN. TWO dispatch formulations, numerically
identical (tests assert bit-level route parity):

* **dense** — routing as one-hot dispatch/combine einsums over an expert-
  capacity buffer [N, E, C]. Expert weights carry a leading [E] dim sharded
  over the mesh's `ep` axis (moe_sharding_rules), so GSPMD lowers the
  dispatch einsum to an all-to-all over ICI. Memory is N·E·C·4 bytes per
  layer activation — at N = 64Ki tokens, E = 64, C = 2048 that is 32 GiB.
* **sorted** — tokens argsorted by expert id (stable, so first-come-first-
  served capacity matches the dense cumsum exactly), scattered into a
  [E·C, d] buffer, batched expert FFN, gathered back. Memory is
  O(E·C·d + N) — the production-scale CTR/MoE formulation (VERDICT r3
  weak #6). Data-dependent scatter indices keep GSPMD from sharding this
  path over `ep`; it is the single-shard / giant-N kernel.

`dispatch_mode` attr: "dense" | "sorted" | "auto" (auto = dense while the
dense dispatch tensor stays under 1 GiB).

Capacity semantics: each expert processes at most
C = ceil(tokens/E * capacity_factor) tokens; overflowing tokens fall
through the residual (output 0 from the MoE branch). An auxiliary
load-balancing loss (importance * load, Switch eq. 4) is returned for the
trainer to add.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .registry import keep_under_recompute, register
from ..framework.dtype import INT64_DEVICE_DTYPE


def _ep_shards() -> int:
    """Expert-parallel shard count of the mesh governing this lowering."""
    from .attention import _current_mesh
    try:
        mesh = _current_mesh()
    except Exception:  # pragma: no cover - no program context
        return 1
    if mesh is not None and "ep" in mesh.axis_names:
        return int(mesh.shape["ep"])
    return 1


def _expert_ffn(xin, w1, b1, w2, b2):
    """Batched per-expert FFN over an [E, C, d] (or [E*C-d reshaped]) buffer."""
    h = jnp.einsum("ecd,edf->ecf", xin, w1.astype(jnp.float32))
    if b1 is not None:
        h = h + b1[:, None, :].astype(jnp.float32)
    h = jax.nn.relu(h)
    out = jnp.einsum("ecf,efd->ecd", h, w2.astype(jnp.float32))
    if b2 is not None:
        out = out + b2[:, None, :].astype(jnp.float32)
    return out


def _rank_in_expert(expert, e, n):
    """FCFS rank of each token within its expert's queue (== the dense
    formulation's `cumsum(onehot)*onehot - 1`), via stable sort instead of
    an [N, E] cumsum."""
    order = jnp.argsort(expert, stable=True)                 # [N]
    se = expert[order]
    starts = jnp.searchsorted(se, jnp.arange(e))             # [E]
    rank_sorted = jnp.arange(n) - starts[se]
    rank = jnp.zeros((n,), rank_sorted.dtype).at[order].set(rank_sorted)
    return rank


def _sorted_dispatch_combine(xt, assignments, w1, b1, w2, b2, e, cap):
    """assignments: list of (expert[N], combine_gate[N], rank[N]) choices.
    Returns combined [N, d] without materializing [N, E, C]."""
    n, d = xt.shape
    buf = jnp.zeros((e * cap + 1, d), jnp.float32)           # +1 overflow sink
    for expert, _gate, rank in assignments:
        keep = rank < cap
        slot = jnp.where(keep, expert * cap + rank, e * cap)
        buf = buf.at[slot].add(xt.astype(jnp.float32))
    out_e = _expert_ffn(buf[:-1].reshape(e, cap, d), w1, b1, w2, b2)
    flat = out_e.reshape(e * cap, d)
    combined = jnp.zeros((n, d), jnp.float32)
    for expert, gate, rank in assignments:
        keep = (rank < cap).astype(jnp.float32)
        slot = jnp.clip(expert * cap + rank, 0, e * cap - 1)
        combined = combined + flat[slot] * (gate * keep)[:, None]
    return combined


@register("switch_moe")
def _switch_moe(ctx, ins, attrs):
    x = ins["X"][0]                        # [b, s, d] or [N, d]
    wg = ins["GateW"][0]                   # [d, E]
    w1 = ins["ExpertW1"][0]                # [E, d, ff]
    b1 = ins.get("ExpertB1", [None])[0]    # [E, ff]
    w2 = ins["ExpertW2"][0]                # [E, ff, d]
    b2 = ins.get("ExpertB2", [None])[0]    # [E, d]
    cf = attrs.get("capacity_factor", 1.25)

    orig_shape = x.shape
    d = x.shape[-1]
    xt = x.reshape(-1, d)                  # [N, d]
    n = xt.shape[0]
    e = w1.shape[0]
    cap = max(1, int(-(-n * cf // e)))     # ceil(n/e * cf)

    top_k = int(attrs.get("top_k", 1))
    if top_k not in (1, 2):
        raise ValueError(
            f"switch_moe supports top_k in (1, 2), got top_k={top_k}")

    gate_logits = xt.astype(jnp.float32) @ wg.astype(jnp.float32)  # [N, E]
    gates = jax.nn.softmax(gate_logits, axis=-1)
    expert = jnp.argmax(gates, axis=-1)                  # [N] top-1
    gate1 = jnp.max(gates, axis=-1)                      # [N]
    if top_k == 2:
        gates2 = gates * (1.0 - jax.nn.one_hot(expert, e,
                                               dtype=jnp.float32))
        expert2 = jnp.argmax(gates2, axis=-1)
        gate2 = jnp.max(gates2, axis=-1)
        denom = jnp.maximum(gate1 + gate2, 1e-9)
        cg1, cg2 = gate1 / denom, gate2 / denom
    else:
        expert2 = gate2 = cg2 = None
        cg1 = gate1

    mode = attrs.get("dispatch_mode", "auto")
    if mode == "auto":
        # under an ep-sharded mesh the DENSE path is the point (GSPMD turns
        # the dispatch einsum into the all-to-all and partitions [N, E, C]
        # over the axis); the sorted path's data-dependent scatter cannot
        # shard over ep, so auto only ever picks it OFF-mesh, and the 1 GiB
        # dispatch-tensor threshold applies to the per-device dense size.
        ep = _ep_shards()
        mode = ("dense" if ep > 1 or n * e * cap * 4 <= (1 << 30)
                else "sorted")

    if mode == "sorted":
        rank1 = _rank_in_expert(expert, e, n)
        assignments = [(expert, cg1, rank1)]
        if top_k == 2:
            # GShard top-2: second choice queues BEHIND all first choices
            count1 = jnp.bincount(expert, length=e)
            rank2 = _rank_in_expert(expert2, e, n) + count1[expert2]
            assignments.append((expert2, cg2, rank2))
        combined = _sorted_dispatch_combine(xt, assignments, w1, b1, w2,
                                            b2, e, cap)
    else:
        onehot = jax.nn.one_hot(expert, e, dtype=jnp.float32)   # [N, E]
        # choice-1 positions in each expert's capacity buffer
        pos1 = jnp.cumsum(onehot, axis=0) * onehot - 1.0        # [N, E]
        keep1 = (pos1 >= 0) & (pos1 < cap)
        pos1_oh = jax.nn.one_hot(pos1.astype(jnp.int32), cap,
                                 dtype=jnp.float32) * keep1[..., None]
        dispatch = onehot[..., None] * pos1_oh                  # [N, E, C]
        combine_w = dispatch * cg1[:, None, None]
        if top_k == 2:
            onehot2 = jax.nn.one_hot(expert2, e, dtype=jnp.float32)
            count1 = jnp.sum(onehot, axis=0)                    # [E]
            pos2 = (jnp.cumsum(onehot2, axis=0) * onehot2 - 1.0
                    + count1[None, :] * onehot2)
            keep2 = (pos2 >= 0) & (pos2 < cap) & (onehot2 > 0)
            pos2_oh = jax.nn.one_hot(pos2.astype(jnp.int32), cap,
                                     dtype=jnp.float32) * keep2[..., None]
            dispatch2 = onehot2[..., None] * pos2_oh
            combine_w = combine_w + dispatch2 * cg2[:, None, None]
            dispatch = dispatch + dispatch2
        # all-to-all happens here when E is sharded over 'ep'
        xin = jnp.einsum("nec,nd->ecd", dispatch, xt.astype(jnp.float32))
        out_e = _expert_ffn(xin, w1, b1, w2, b2)
        combined = jnp.einsum("nec,ecd->nd", combine_w, out_e)

    out = combined.astype(x.dtype)

    # Switch aux loss (eq. 4) / GShard me*ce: both use the TOP-1 assignment
    importance = jnp.mean(gates, axis=0)                  # [E]
    load = jnp.mean(jax.nn.one_hot(expert, e, dtype=jnp.float32), axis=0)
    aux = e * jnp.sum(importance * load)

    return {"Out": [out.reshape(orig_shape)],
            "AuxLoss": [aux.astype(x.dtype)],
            "GateIdx": [expert.astype(INT64_DEVICE_DTYPE)]}


# ---------------------------------------------------------------------------
# routed_moe: the expert layer as sparse decoder LMs deploy it (DeepSeek-V3
# family): sigmoid scores (or a softmax over all the experts), a selection
# bias no gradient reaches (or none), top-k of ALL experts (or, `n_group` >
# 1, of the experts in the best `topk_group` groups), normalised and
# scaled weights, no capacity and no drops,
# gated experts (or, given no gate matrix, experts of the form
# W_down relu(W_up x)^2), and the share of one expert-parallel rank: told
# which experts it holds, it routes over all of them and computes its own
# part.
#
# The route moves no scalar by a gather or a scatter, forward or backward.
# On a TPU v5e those are priced by the element and not by the byte: one of
# k*N scalars takes 0.23 to 0.46 ms at 49,152 slots (PR 29's trace) and 0.42
# (the inverse permutation's scatter) to 0.92 ms (`take_along_axis` out of
# [4096, 512]) at 90,112 (PR 41's), what moving 300 to 750 MB takes, where
# a sort of the same slots takes 0.08 to 0.10 and carries a payload for
# nothing, and a select reduced inside one fusion 0.08. So the route uses a
# reduce or a sort's payload: the slots' weights are picked by a one-hot
# reduce (`_slot_weights`), ride through the stable sort by held expert as
# its payload and get their gradient back by a sort on the permutation
# (`_sort_slots`), which is also how the permutation is inverted
# (`_unsort`). The gather form is the oracle of `tests/test_moe_route.py`,
# bit for bit, where a step's scalar moves are counted too (none).
# ---------------------------------------------------------------------------

# What `routed_moe`'s forward writes for its grad rule (beside `TopIdx` and
# `ExpertLoad`, which a caller may fetch): the gate and up projections of
# the sorted rows, [rows, f] in the compute dtype (`H` only where the experts
# have a gate), the slots' weights in sorted order, and the sort with its
# inverse. Narrow each: never a [rows, d] buffer. rows = min(k, E_held) * N:
# a token cannot pick one expert twice, so more assignments than that never
# arrive here (`_token_rows`).
_RESIDUALS = ("H", "U", "SortedW", "Order", "Inv", "TopIdx", "ExpertLoad")

# dW of a grouped matmul: x [m, a] and g [m, b] contracted over the ragged
# rows, group by group -> [E, a, b] (what JAX's transpose rule emits)
_DW_DIMS = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(([0], [0]), ([], [])),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


class _RowGroups:
    """The groups of one sorted row buffer, for every grouped matmul a
    layer runs over it in one direction: the sizes over the WHOLE buffer
    (`_whole_buffer`) and, made once per row tile, the Pallas kernels' visit
    tables. A matmul goes to `ops/pallas/grouped_matmul.py` where that
    file's tile rule takes the operands' shapes (widths of a lane tile, 128,
    or more; one that is no multiple of 128 as a single full-width block)
    and to `jax.lax.ragged_dot` where it returns None; `count`:
    whether this trace's calls count, `moe.grouped_pallas` /
    `moe.grouped_xla`, once per grouped matmul lowered."""

    def __init__(self, sizes, rows, count):
        self.sizes, self.rows, self.count = (_whole_buffer(sizes, rows),
                                             rows, count)
        self._visits = {}

    def plan(self, rule, x, k, n, out_dtype):
        """(the rule's tiles for x [rows, k] against n columns, the visit
        tables under their row tile), or (None, None)."""
        tiles = rule(self.rows, k, n, x.dtype.itemsize,
                     jnp.dtype(out_dtype).itemsize)
        if self.count:
            from ..observability import metrics
            metrics.inc("moe.grouped_xla" if tiles is None
                        else "moe.grouped_pallas")
        if tiles is None:
            return None, None
        if tiles.tm not in self._visits:
            from .pallas.grouped_matmul import group_visits
            self._visits[tiles.tm] = group_visits(self.sizes, self.rows,
                                                  tiles.tm)
        return tiles, self._visits[tiles.tm]


def _grouped(x, w, groups, transposed=False):
    """x [m, K] times its group's w[e]: w [E, K, N], or (`transposed`: the
    dx of a grouped matmul) w [E, N, K] read as its transpose."""
    from .pallas import grouped_matmul as kernels
    k, n = (w.shape[2], w.shape[1]) if transposed else w.shape[1:]
    tiles, visits = groups.plan(kernels.gmm_tiles, x, k, n, w.dtype)
    if tiles is None:
        return jax.lax.ragged_dot(
            x, jnp.swapaxes(w, 1, 2) if transposed else w, groups.sizes,
            preferred_element_type=w.dtype)
    return kernels.gmm(x, w, visits, tiles=tiles, transpose_rhs=transposed,
                       out_dtype=w.dtype)


def _grouped_dw(x, g, groups):
    from .pallas import grouped_matmul as kernels
    tiles, visits = groups.plan(kernels.tgmm_tiles, x, x.shape[1],
                                g.shape[1], g.dtype)
    if tiles is None:
        return jax.lax.ragged_dot_general(x, g, groups.sizes, _DW_DIMS,
                                          preferred_element_type=g.dtype)
    return kernels.tgmm(x, g, visits, tiles=tiles, out_dtype=g.dtype)


def _sum_slots(rows, k):
    """[k*N, d] slot-major -> the float32 sum [N, d] of its k blocks, block
    by block: no float32 value of the buffer's own size."""
    n = rows.shape[0] // k
    return sum(rows[j * n:(j + 1) * n].astype(jnp.float32) for j in range(k))


def _whole_buffer(sizes, rows):
    """sizes [E_held]: assignments on each held expert. The grouped matmuls
    cover the whole k*N-row buffer: the last group also takes the foreign
    slots' rows."""
    return sizes.at[-1].add(rows - jnp.sum(sizes))


def _token_rows(inv, top_k, e_held):
    """What the combine gathers by. inv [k*N]: the sorted row of every slot
    (slot-major). With k <= E_held that is it, and the buffer has k*N rows.
    With k > E_held a token has at most E_held slots on a held expert, and
    the sort put the held slots first: the buffer is the first E_held * N
    sorted rows, and a token's held slots are among its E_held lowest rows,
    [E_held * N] slot-major (the others of them foreign: rows of zeros, or
    past the buffer's end)."""
    if top_k <= e_held:
        return inv
    return jnp.sort(inv.reshape(top_k, -1), axis=0)[:e_held].reshape(-1)


def _buffer(a, rows):
    """The first `rows` of a value in sorted order: all of it where the
    buffer holds every slot."""
    return a if a.shape[0] == rows else a[:rows]


def _gather_rows(y, inv, bounded):
    """y [rows, d] at inv; `bounded`: a row past the buffer's end (a
    foreign slot) reads zeros."""
    return y.at[inv].get(mode="fill", fill_value=0) if bounded else y[inv]


def _weighted_act(h, u, w_sorted):
    """silu(h) * u in float32 (h None, an expert without a gate:
    relu(u)^2), and the same times its slot's weight rounded once to the
    compute dtype: the down projection's operand."""
    if h is None:
        act = jnp.square(jax.nn.relu(u.astype(jnp.float32)))
    else:
        act = jax.nn.silu(h.astype(jnp.float32)) * u.astype(jnp.float32)
    return act, (act * w_sorted[:, None]).astype(u.dtype)


def _experts_fwd(count, xt, w_sorted, order, inv, sizes, eg, eu, ed):
    """sum_k w_k E_{i_k}(x) over the slots whose expert is held here, and
    the residuals (h, u).

    xt [N, d]; order: the permutation that sorts the k*N slots
    (slot-major: slot j of token i is row j*N + i) by held expert (foreign
    slots last); inv [rows]: what the combine gathers by, the inverse of
    `order` or its bounded form (`_token_rows`), rows = min(k, E_held) * N;
    w_sorted [k*N] float32: the slots' weights in sorted order (0 where the
    slot's expert is elsewhere); sizes [E_held]. The buffers hold every
    assignment there can be and the grouped matmuls run over all of them,
    the foreign slots' rows as zeros in the last group: what a step costs
    is fixed by its shapes and not by the routing (with k <= E_held a
    rank's k*N rows are also what its experts see in the deployment, where
    the exchange fills them), and a zero row yields a zero row, so nothing
    needs a mask but the gathered input. The slot's weight goes in AHEAD of
    the down projection, w (a W) = (w a) W over f columns, so the combine
    is a plain sum of a token's slots. `eg` None: the experts have no gate,
    W_down relu(W_up x)^2, two grouped matmuls, and h is None. `count`:
    this trace's grouped matmuls count (`_RowGroups`)."""
    rows, n = inv.shape[0], xt.shape[0]
    groups = _RowGroups(sizes, rows, count)
    with jax.named_scope("moe.dispatch"):
        valid = jnp.arange(rows) < jnp.sum(sizes)
        xs = jnp.where(valid[:, None],
                       xt.astype(eu.dtype)[_buffer(order, rows) % n], 0)
    with jax.named_scope("moe.experts"):
        h = None if eg is None else _grouped(xs, eg, groups)
        u = _grouped(xs, eu, groups)
        _, wa = _weighted_act(h, u, _buffer(w_sorted, rows))
        y = _grouped(wa, ed, groups)
    with jax.named_scope("moe.combine"):
        return _sum_slots(_gather_rows(y, inv, order.shape[0] != rows),
                          rows // n), h, u


def _experts_bwd(count, xt, w_sorted, order, inv, sizes, eg, eu, ed, h, u,
                 g):
    """The transpose of `_experts_fwd` at g = d Out [N, d], on the h and u
    it wrote: six grouped matmuls (four without a gate), none of the
    forward's again. No mask: a foreign slot's weight is 0, so its rows of
    dh and du are. Returns the gradients of (xt, w_sorted, eg, eu, ed)."""
    rows, n = inv.shape[0], xt.shape[0]
    bounded = order.shape[0] != rows
    cdt = eu.dtype
    # what is read here is read when the backward gets here: without the
    # barrier XLA merges the gather of xs below with the forward's and
    # keeps a [rows, d] buffer a layer alive in between
    g, xt, order, inv, h, u = jax.lax.optimization_barrier(
        (g, xt, order, inv, h, u))
    groups = _RowGroups(sizes, rows, count)
    held, w_held = _buffer(order, rows), _buffer(w_sorted, rows)
    with jax.named_scope("moe.combine"):
        gs = g.astype(cdt)[held % n]                          # [rows, d]
    with jax.named_scope("moe.dispatch"):
        xs = xt.astype(cdt)[held % n]
    with jax.named_scope("moe.experts"):
        act, wa = _weighted_act(h, u, w_held)
        ded = _grouped_dw(wa, gs, groups)
        dwa = _grouped(gs, ed, groups, transposed=True).astype(jnp.float32)
        dw_sorted = jnp.sum(dwa * act, axis=1)
        dact = dwa * w_held[:, None]
        if eg is None:
            du = (dact * 2.0 * jax.nn.relu(u.astype(jnp.float32))).astype(cdt)
            deg = None
            deu = _grouped_dw(xs, du, groups)
            dxs = _grouped(du, eu, groups, transposed=True)
        else:
            hf = h.astype(jnp.float32)
            sig = jax.nn.sigmoid(hf)
            dh = (dact * u.astype(jnp.float32)
                  * sig * (1.0 + hf * (1.0 - sig))).astype(cdt)
            du = (dact * hf * sig).astype(cdt)
            deg = _grouped_dw(xs, dh, groups)
            deu = _grouped_dw(xs, du, groups)
            dxs = (_grouped(dh, eg, groups, transposed=True)
                   + _grouped(du, eu, groups, transposed=True))
    with jax.named_scope("moe.dispatch"):
        dxt = _sum_slots(_gather_rows(dxs, inv, bounded), rows // n)
    if bounded:      # the slots past the buffer's end are foreign: weight 0
        dw_sorted = jnp.pad(dw_sorted, (0, order.shape[0] - rows))
    return dxt.astype(xt.dtype), dw_sorted, deg, deu, ded


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _held_experts(count, xt, w_sorted, order, inv, sizes, eg, eu, ed):
    return _experts_fwd(count, xt, w_sorted, order, inv, sizes, eg, eu, ed)


def _held_experts_fwd(count, *args):
    out, h, u = _experts_fwd(count, *args)
    return (out, h, u), args + (h, u)


def _held_experts_bwd(count, res, cts):
    dxt, dw, deg, deu, ded = _experts_bwd(count, *res, cts[0])
    return dxt, dw, None, None, None, deg, deu, ded


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


def _geometry(ins, attrs):
    wg = ins["GateW"][0]                    # [d, E_total]
    e_total = int(attrs.get("experts_total", wg.shape[1]))
    off = int(attrs.get("expert_offset", 0))
    e_held = ins["ExpertUp"][0].shape[0]
    if wg.shape[1] != e_total or off < 0 or off + e_held > e_total:
        raise ValueError(
            f"routed_moe: GateW routes over {wg.shape[1]} experts, "
            f"experts_total={e_total}, held {off}..{off + e_held}")
    return off, e_held


def _scores(xt, wg, scoring="sigmoid"):
    """[N, E] float32 over ALL the experts: "sigmoid" of each logit, or
    "softmax" over the experts."""
    logits = jnp.dot(xt.astype(jnp.float32), wg.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if scoring == "sigmoid":
        return jax.nn.sigmoid(logits)
    if scoring == "softmax":
        return jax.nn.softmax(logits, axis=-1)
    raise ValueError(f"routed_moe: unknown scoring {scoring!r}")


def _group_limited(sel, n_group, topk_group):
    """sel [N, E] with every expert outside the best `topk_group` of
    `n_group` equal groups of consecutive experts at -inf; a group's score
    is the sum of its two highest entries."""
    n, e = sel.shape
    if e % n_group or not 0 < topk_group <= n_group:
        raise ValueError(f"routed_moe: {e} experts in {n_group} groups, "
                         f"{topk_group} of them kept")
    grouped = sel.reshape(n, n_group, e // n_group)
    best_two, _ = jax.lax.top_k(grouped, 2)
    _, kept = jax.lax.top_k(jnp.sum(best_two, axis=-1), topk_group)
    keep = jnp.any(kept[:, :, None] == jnp.arange(n_group), axis=1)
    return jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(n, e)


def _slot_weights(scores, idx, local, attrs):
    """[k, N] float32: the chosen experts' scores, normalised and scaled; 0
    where the slot's expert is held elsewhere. scores[n, idx[n, j]] as a
    reduce over the experts of a select (no gather: the note above): a
    token picks no expert twice, so every sum has one term that is not 0
    and is the gather's value bit for bit; JAX's transpose is the same
    select reduced over the slots, the scatter-add's bit for bit. XLA keeps
    the [N, k, E] select inside the reduce's fusion; the barrier keeps it
    from folding the sum over a token's slots below into that reduce, one
    sum over (slot, expert) that rounds in another order."""
    picked = idx[:, :, None] == jnp.arange(scores.shape[1])
    w = jax.lax.optimization_barrier(
        jnp.sum(jnp.where(picked, scores[:, None, :], 0.0), axis=2))
    if attrs.get("norm_topk", True):
        w = w / (jnp.sum(w, axis=1, keepdims=True)
                 + float(attrs.get("norm_topk_eps", 1e-20)))
    w = w * float(attrs.get("routed_scaling", 1.0))
    return jnp.where(local, w, 0.0).T


def _unsort(order, a_sorted):
    """a_sorted [k*N] back in slot order: sorted by the permutation itself,
    row j lands at order[j]. Of the slots' own numbers that is the inverse
    permutation."""
    return jax.lax.sort((order, a_sorted), num_keys=1)[1]


@jax.custom_vjp
def _sort_slots(eid, w):
    """(order, w_sorted): the stable argsort of eid [k*N], and the slots'
    weights w in that order, carried along as the sort's payload. The
    payload's gradient comes back by `_unsort` (JAX's own rule for a sort
    gathers the tangent by `order`, and its transpose scatters)."""
    slots = jnp.arange(eid.shape[0], dtype=jnp.int32)
    _, order, w_sorted = jax.lax.sort((eid, slots, w), num_keys=1,
                                      is_stable=True)
    return order, w_sorted


def _sort_slots_fwd(eid, w):
    # kept by a recomputed segment, marked before they are result AND
    # residual: one sort gives both, so one of them read unkept in the
    # backward (`_unsort` by order here, the experts' weights) would run it
    # again
    order, w_sorted = map(keep_under_recompute, _sort_slots(eid, w))
    return (order, w_sorted), order


def _sort_slots_bwd(order, cts):
    return None, _unsort(order, cts[1])


_sort_slots.defvjp(_sort_slots_fwd, _sort_slots_bwd)


def _expert_input(ins, xt):
    """(`ExpertX` [..., d_e] or None, the rows the experts read [N, d_e]):
    what the experts read and write where it is not what the router scores
    (e.g. a latent of `X`); None and the router's own rows `xt` without."""
    xe = ins["ExpertX"][0] if ins.get("ExpertX") else None
    return xe, xt if xe is None else xe.reshape(-1, xe.shape[-1])


def _expert_weights(ins):
    """(gate, up, down) [E_held, ...]; gate None where the op was given no
    `ExpertGate`: experts of the form W_down relu(W_up x)^2."""
    eg = ins["ExpertGate"][0] if ins.get("ExpertGate") else None
    return eg, ins["ExpertUp"][0], ins["ExpertDown"][0]


def _routed_moe_grad(ctx, ins, attrs, outs, ogs):
    """Grad rule: the backward on what the forward wrote (`_RESIDUALS`).
    The experts' part is `_experts_bwd`; the router's (GateW, and x through
    the scores) is the small weight function differentiated alone at the
    fixed `TopIdx`; with `ExpertX` each input gets its own path's. Declines
    when a residual is absent (a program built before they existed), and
    the generic `__vjp__` differentiates the forward lowering."""
    g = (ogs.get("Out") or [None])[0]
    eg, eu, ed = _expert_weights(ins)
    if g is None or not all(outs.get(s) for s in _RESIDUALS
                            if s != "H" or eg is not None):
        return None
    x, wg = ins["X"][0], ins["GateW"][0]
    h, u, w_sorted, order, inv, idx, sizes = (
        outs[s][0] if outs.get(s) else None for s in _RESIDUALS)
    off, e_held = _geometry(ins, attrs)
    xt = x.reshape(-1, x.shape[-1])
    xe, xet = _expert_input(ins, xt)
    local = (idx >= off) & (idx < off + e_held)
    dxt, dw_sorted, deg, deu, ded = _experts_bwd(
        not ctx.is_eval_shape, xet, w_sorted, order, inv, sizes, eg, eu, ed,
        h, u, g.reshape(xet.shape))
    with jax.named_scope("moe.route"):
        dw = _unsort(order, dw_sorted)
        _, route_vjp = jax.vjp(
            lambda xt, wg: _slot_weights(
                _scores(xt, wg, attrs.get("scoring", "sigmoid")), idx, local,
                attrs), xt, wg)
        dxt_route, dwg = route_vjp(dw.reshape(idx.shape[1], xt.shape[0]))
    if not ctx.is_eval_shape:
        from ..observability import metrics
        metrics.inc("moe.bwd_residual")
    grads = {"GateW": [dwg], "ExpertUp": [deu], "ExpertDown": [ded]}
    if xe is None:
        grads["X"] = [(dxt + dxt_route).reshape(x.shape)]
    else:        # the route's gradient to what it read, the experts' to theirs
        grads.update(X=[dxt_route.reshape(x.shape)],
                     ExpertX=[dxt.reshape(xe.shape)])
    if eg is not None:
        grads["ExpertGate"] = [deg]
    return grads


@register("routed_moe", nondiff_slots=("SelectBias",),
          grad=_routed_moe_grad, residual_slots=_RESIDUALS)
def _routed_moe(ctx, ins, attrs):
    x = ins["X"][0]                         # [..., d]
    wg = ins["GateW"][0]                    # [d, E_total]
    bias = ins["SelectBias"][0] if ins.get("SelectBias") else None
    eg, eu, ed = _expert_weights(ins)       # [E_held, ...]
    top_k = int(attrs["top_k"])
    off, e_held = _geometry(ins, attrs)
    d = x.shape[-1]
    xt = x.reshape(-1, d)
    n = xt.shape[0]
    xe, xet = _expert_input(ins, xt)
    if xet.shape[0] != n:
        raise ValueError(f"routed_moe: ExpertX has {xet.shape[0]} rows, X {n}")

    with jax.named_scope("moe.route"):
        scores = _scores(xt, wg, attrs.get("scoring", "sigmoid"))
        sel = jax.lax.stop_gradient(scores)
        if bias is not None:
            sel = sel + bias.astype(jnp.float32)
        n_group = int(attrs.get("n_group", 1))
        if n_group > 1:
            sel = _group_limited(sel, n_group, int(attrs["topk_group"]))
        # the route's choices are kept by a recomputed segment
        # (`keep_under_recompute`; the sort's two results in
        # `_sort_slots_fwd`): a few bytes a slot against a top_k and three
        # sorts, marked before `_held_experts` takes them as residuals
        idx = keep_under_recompute(jax.lax.top_k(sel, top_k)[1])  # [N, k]
        local = (idx >= off) & (idx < off + e_held)
        w_slot = _slot_weights(scores, idx, local, attrs)    # [k, N]
        eid = jnp.where(local, idx - off, e_held).T.reshape(-1)  # [k*N]
        sizes = keep_under_recompute(jnp.sum(
            eid[:, None] == jnp.arange(e_held)[None, :], axis=0,
            dtype=jnp.int32))                                # [E_held]
        order, w_sorted = _sort_slots(eid, w_slot.reshape(-1))
        inv = keep_under_recompute(_token_rows(
            _unsort(order, jnp.arange(n * top_k, dtype=jnp.int32)),
            top_k, e_held))

    out, h, u = _held_experts(not ctx.is_eval_shape, xet, w_sorted, order,
                              inv, sizes, eg, eu, ed)
    if not ctx.is_eval_shape:
        from ..observability import metrics
        # in_vjp: the generic __vjp__ lowers this forward again to
        # differentiate it (the rule declined, or a whole segment is
        # differentiated at once)
        metrics.inc("moe.bwd_recomputed" if ctx.in_vjp
                    else "moe.layers_lowered")
        if n_group > 1 and not ctx.in_vjp:
            metrics.inc("moe.group_limited_layers")
        if top_k > e_held and not ctx.in_vjp:
            metrics.inc("moe.rows_bounded")
        if xe is not None and not ctx.in_vjp:
            metrics.inc("moe.latent_layers_lowered")
    outs = {"Out": [out.astype(eu.dtype).reshape(
                x.shape if xe is None else xe.shape)],
            "TopIdx": [idx.astype(INT64_DEVICE_DTYPE)],
            "ExpertLoad": [sizes], "U": [u],
            "SortedW": [w_sorted], "Order": [order], "Inv": [inv]}
    if eg is not None:
        outs["H"] = [h]
    return outs
