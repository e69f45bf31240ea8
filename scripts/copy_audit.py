#!/usr/bin/env python
"""Census of the copy ops in the compiled train step (the round-5 lead).

Round 5 closed with a ~20x framework-vs-pure-jax throughput gap whose
named suspect was the compiled step's schedule: 961 copy-done / 876
async-done ops in the 20-step BERT dispatch vs a compact pure-jax scan
body (docs/perf_notes.md "Round 5", VERDICT round 5). Like the
collective census (scripts/collective_audit.py), the copy population is
fully auditable from optimized HLO on the virtual CPU mesh — no
hardware needed. This script compiles the bench BERT train step (single
step AND the run_steps k-step dispatch, optionally rolled with
layer_scan), finds every copy / copy-start / copy-done / async-done op,
and classifies 100% of them by cause:

  entry-param-staging   a copy of an entry parameter: either a DONATED
                        buffer whose in-place update's live range crosses
                        a remaining read (XLA preserves the old value), or
                        an un-donated input staged into a loop carry.
                        Driven toward zero by the executor's donation
                        floor (FLAGS_min_donate_bytes) + the shared Adam
                        beta-pow pair (optimizer.py).
  step-state-inplace    a copy inside the training-loop scan body of a
                        small piece of carried state: the per-step
                        in-place update of a tiny buffer (LN scale/bias,
                        beta pows) conflicts with a remaining reader of
                        the old value, so XLA preserves it. Paid EVERY
                        step — the budget tests/test_copy_budget.py
                        asserts bounds.
  loop-activation       float copies >1 KB inside a loop body: XLA
                        scheduling/layout staging of per-step tensors.
  rng-counter           integer-typed copies (u32/s32): threefry loop
                        state on the CPU backend (the TPU path uses the
                        single-pass RngBitGenerator, ops/rng.py) and
                        scan induction counters.
  fused-layout          copies INSIDE fusion computations: materialized
                        layout changes fused into surrounding compute —
                        they never schedule as standalone ops.
  fetch-staging         copies feeding the entry ROOT tuple: staging a
                        fetch that aliases state.
  scheduling-other      anything else — XLA scheduling residue that no
                        framework-layer decision controls.

Usage (a CPU tool on any machine: re-execs into a CPU-mesh child when the
environment is not one, and says on stderr that it ran on the CPU):

  JAX_PLATFORMS=cpu python scripts/copy_audit.py            # census rows
  python scripts/copy_audit.py --bench                      # bench geometry
  python scripts/copy_audit.py --layers 8 --k 20 --layer-scan
"""
from __future__ import annotations

import argparse
import collections
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

DT_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f64": 8,
            "pred": 1, "s8": 1, "u8": 1, "s64": 8, "u64": 8}

COPY_KINDS = ("copy-start", "copy-done", "copy", "async-done")
# per-step-state size bound: in-place updates of buffers up to this many
# bytes inside a loop body read as tiny-state conflicts, larger ones as
# activation staging
SMALL_STATE_BYTES = 4096


def _shape_bytes(ty: str) -> int:
    m = re.match(r"(\w+)\[([\d,]*)\]", ty)
    if not m:
        return 0
    dt, dims = m.groups()
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * DT_BYTES.get(dt, 4)


def _parse_computations(txt: str):
    """HLO text -> {comp_name: [instruction lines]}, entry comp name,
    loop-body comp names, fusion comp names."""
    comps: "collections.OrderedDict[str, list]" = collections.OrderedDict()
    comp = None
    entry = None
    for line in txt.splitlines():
        if line and not line.startswith(" ") and "{" in line:
            m = re.match(r"\s*(ENTRY )?(%?[\w\.\-]+)", line)
            if m:
                comp = m.group(2)
                comps[comp] = []
                if m.group(1):
                    entry = comp
            continue
        if comp is not None and line.strip() and line.strip() != "}":
            comps[comp].append(line)

    loop_bodies, fusion_comps = set(), set()
    for name, lines in comps.items():
        for line in lines:
            m = re.search(r"body=(%?[\w\.\-]+)", line)
            if m:
                loop_bodies.add(m.group(1).lstrip("%"))
            m = re.search(r"calls=(%?[\w\.\-]+).*kind=", line)
            if m:
                fusion_comps.add(m.group(1).lstrip("%"))
        # fusion computations are also recognizable by name
        if "fused_computation" in name:
            fusion_comps.add(name.lstrip("%"))
    return comps, entry, loop_bodies, fusion_comps


def copy_census(txt: str):
    """Classify every copy/copy-start/copy-done/async-done op by cause.

    Returns (by_cause_counts, by_cause_bytes, per_step_count, total).
    per_step_count = copies inside loop-body computations (paid every
    iteration of the training-loop scan); everything else is paid once
    per dispatch. 100% of found copies land in a bucket (the script
    asserts it).
    """
    comps, entry, loop_bodies, fusion_comps = _parse_computations(txt)

    # operand-opcode map for the entry computation (donation analysis)
    entry_defs = {}
    root_line = ""
    for line in comps.get(entry, []):
        m = re.search(r"%([\w\.\-]+) = \S+ ([\w\-]+)", line)
        if m:
            entry_defs[m.group(1)] = m.group(2)
        if "ROOT" in line:
            root_line = line

    counts = collections.Counter()
    byte_tot = collections.Counter()
    per_step = 0
    total = 0
    for name, lines in comps.items():
        bare = name.lstrip("%")
        in_loop = any(bare.startswith(b) or b.startswith(bare)
                      for b in loop_bodies) or "region" in bare \
            or "while_body" in bare
        in_fusion = bare in {f for f in fusion_comps} \
            or "fused_computation" in bare
        is_entry = name == entry
        for line in lines:
            m = re.search(
                r"%([\w\.\-]+) = (\S+?) (copy-start|copy-done|copy|"
                r"async-done)\((\S+?) %?([\w\.\-]+)", line)
            if not m:
                continue
            iname, ty, kind, _oty, operand = m.groups()
            # copy-start results are tuple-typed "(f32[...], f32[...],
            # u32[])" — size the first element (the payload)
            nbytes = _shape_bytes(ty.lstrip("("))
            total += 1
            dt = ty.split("[")[0]
            if in_fusion:
                cause = "fused-layout"
            elif dt in ("u32", "s32", "u8", "pred", "s64", "u64"):
                cause = "rng-counter"
            elif in_loop:
                per_step += 1
                cause = ("step-state-inplace"
                         if nbytes <= SMALL_STATE_BYTES
                         else "loop-activation")
            elif is_entry:
                if entry_defs.get(operand) == "parameter":
                    cause = "entry-param-staging"
                elif f"%{iname}" in root_line:
                    cause = "fetch-staging"
                else:
                    cause = "scheduling-other"
            else:
                cause = "scheduling-other"
            counts[cause] += 1
            byte_tot[cause] += nbytes
    assert sum(counts.values()) == total, "copy census lost ops"
    return counts, byte_tot, per_step, total


def build_and_census(layers, hidden, heads, ffn, batch, seq, vocab,
                     k=0, layer_scan=False, dropout=0.1):
    """Build + compile the BERT train step (bench recipe: AMP + Adam) and
    return its copy census plus total instruction count."""
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import bert
    from paddle_tpu.distributed import fleet
    from paddle_tpu.testing import reset_programs

    reset_programs(seed=0)
    cfg = bert.BertConfig(vocab_size=vocab, hidden_size=hidden,
                          num_layers=layers, num_heads=heads,
                          intermediate_size=ffn,
                          max_position=max(seq, 32), seq_len=seq,
                          hidden_dropout=dropout, attention_dropout=dropout)
    ids, labels, loss = bert.build_pretrain_program(cfg)
    fleet.init(is_collective=True)
    strategy = fleet.DistributedStrategy()
    strategy.amp = True
    strategy.layer_scan = layer_scan
    opt = fleet.distributed_optimizer(
        paddle.optimizer.Adam(learning_rate=1e-4), strategy)
    opt.minimize(loss)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    feed = {"input_ids": rng.randint(0, cfg.vocab_size,
                                     (batch, seq)).astype(np.int64),
            "mlm_labels": rng.randint(0, cfg.vocab_size,
                                      (batch, seq, 1)).astype(np.int64)}
    txt = exe.compiled_hlo(feed, [loss], k=k if k and k > 1 else None)
    counts, byte_tot, per_step, total = copy_census(txt)
    n_instr = sum(1 for line in txt.splitlines() if " = " in line)
    return counts, byte_tot, per_step, total, n_instr


def serving_census(max_slots=4, block_size=8, num_blocks=64, max_len=64,
                   window=8, dtype="float32"):
    """Census of the serving decode-window program (the paged-KV analog of
    the train-step census): build the tiny-GPT decode engine
    (paddle_tpu/serving/), AOT-compile its window program, and count
    pool-shaped copies — the HLO signature of a failed cache donation.
    Zero is the acceptance bar (serving/audit.py); the full copy
    population is reported for context."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models.gpt import GPTConfig, build_lm_program
    from paddle_tpu.models.gpt_decode import params_from_scope
    from paddle_tpu.serving import DecodeEngine
    from paddle_tpu.serving import audit
    from paddle_tpu.testing import reset_programs

    reset_programs(seed=0)
    cfg = GPTConfig.tiny()
    cfg.max_position = max(cfg.max_position, max_len)
    build_lm_program(cfg)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    params = params_from_scope(cfg)
    engine = DecodeEngine(params, cfg,
                          max_slots=max_slots, block_size=block_size,
                          num_blocks=num_blocks, max_len=max_len,
                          window=window, dtype=dtype)
    row = audit.decode_copy_census(engine)
    row["dense_gathers_fallback"] = \
        audit.decode_gather_census(engine)["dense_gathers"]
    # the fused-kernel twin: same geometry, decode_kernel on — the dense
    # cache-view census must come back EMPTY (serving/audit.py)
    kengine = DecodeEngine(params, cfg,
                           max_slots=max_slots, block_size=block_size,
                           num_blocks=num_blocks, max_len=max_len,
                           window=window, dtype=dtype, decode_kernel=True)
    row["dense_gathers_kernel"] = \
        audit.decode_gather_census(kengine)["dense_gathers"]
    # the speculative verify program (serving/spec.py): BOTH census arms
    # extend to the second pool-touching compiled surface — zero
    # pool-shaped copies on the fallback arm, zero dense cache-view
    # materializations on the fused-kernel arm (the kernel-on pool-copy
    # census is skipped for the same interpret-mode reason as the window's)
    vrow = audit.verify_copy_census(engine)
    row["verify_span"] = vrow["span"]
    row["verify_pool_copies"] = vrow["pool_copies"]
    row["verify_dense_gathers_fallback"] = \
        audit.verify_gather_census(engine)["dense_gathers"]
    row["verify_dense_gathers_kernel"] = \
        audit.verify_gather_census(kengine)["dense_gathers"]
    return row


def _fmt_row(tag, counts, byte_tot, per_step, total, n_instr):
    parts = ", ".join(f"{c} x{counts[c]} ({byte_tot[c] / 1e3:.1f} KB)"
                      for c in sorted(counts)) or "none"
    return (f"{tag:24s} copies {total:5d} (per-step {per_step:4d}) "
            f"of {n_instr} instrs: {parts}")


def main():
    ap = argparse.ArgumentParser(
        description="copy census of the compiled BERT train step")
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--ffn", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--k", type=int, default=20,
                    help="run_steps window for the k-step dispatch row")
    ap.add_argument("--dropout", type=float, default=0.1)
    ap.add_argument("--layer-scan", action="store_true",
                    help="add a rolled-layer (lax.scan over layers) row")
    ap.add_argument("--bench", action="store_true",
                    help="audit the full bench geometry (BERT-base 12L/768H"
                         " batch 128 seq 128) — minutes of CPU XLA compile")
    ap.add_argument("--serving", action="store_true",
                    help="census the serving decode-window program instead "
                         "(paddle_tpu/serving/): exit 1 if any pool-shaped "
                         "copy — a per-token KV-cache copy — survives")
    args = ap.parse_args()

    from paddle_tpu.testing import run_as_cpu_tool
    run_as_cpu_tool(1, os.path.abspath(__file__), sys.argv[1:])

    if args.serving:
        row = serving_census()
        pop = ", ".join(f"{k} x{v}" for k, v in
                        sorted(row["copy_population"].items()) if v) \
            or "none"
        print(f"serving decode window (W={row['window']}, pool "
              f"{row['pool_shape']}): per-token KV copies "
              f"{row['per_token_kv_copies']} of {row['instructions']} "
              f"instrs; copy population: {pop}")
        for f in row["kv_copy_findings"]:
            print(f"  KV COPY: {f['kind']} {f['instruction']} "
                  f"{f['dims']}")
        print(f"dense cache-view census: fallback "
              f"{row['dense_gathers_fallback']} materializations, fused "
              f"kernel {row['dense_gathers_kernel']} (bar: 0)")
        print(f"speculative verify (span={row['verify_span']}): pool "
              f"copies {row['verify_pool_copies']}; dense gathers "
              f"fallback {row['verify_dense_gathers_fallback']}, fused "
              f"kernel {row['verify_dense_gathers_kernel']} (bar: 0)")
        sys.exit(1 if (row["per_token_kv_copies"]
                       or row["dense_gathers_kernel"]
                       or row["verify_pool_copies"]
                       or row["verify_dense_gathers_kernel"]) else 0)

    if args.bench:
        geo = dict(layers=12, hidden=768, heads=12, ffn=3072,
                   batch=128, seq=128, vocab=30522)
    else:
        geo = dict(layers=args.layers, hidden=args.hidden, heads=args.heads,
                   ffn=args.ffn, batch=args.batch, seq=args.seq,
                   vocab=args.vocab)
    desc = (f"BERT L={geo['layers']} H={geo['hidden']} batch={geo['batch']} "
            f"seq={geo['seq']} dropout={args.dropout}")
    print(f"copy census: {desc} (Adam, AMP; virtual CPU mesh)")

    rows = [("single-step", dict(k=0)),
            (f"run_steps k={args.k}", dict(k=args.k))]
    if args.layer_scan:
        rows.append((f"rolled k={args.k}", dict(k=args.k, layer_scan=True)))
    for tag, kw in rows:
        try:
            res = build_and_census(dropout=args.dropout, **geo, **kw)
        except Exception as e:     # one broken row must not kill the audit
            print(f"{tag:24s} FAILED ({e!r:.120})")
            continue
        print(_fmt_row(tag, *res))


if __name__ == "__main__":
    main()
