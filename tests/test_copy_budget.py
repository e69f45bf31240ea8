"""Asserted copies-per-step budget for the compiled train step.

Round 5's ~20x framework-vs-pure-jax anomaly named the compiled step's
copy population (961 copy-done ops in the 20-step BERT dispatch) as the
lead suspect, and the fix landed in three parts: the shared Adam
beta-pow pair (optimizer.py — one [1]-buffer pair instead of 2N, each of
which cost an in-place-aliasing copy EVERY step inside the training-loop
scan), the donation size floor (framework/executor.py
FLAGS_min_donate_bytes — tiny written state is passed un-donated in the
per-step path so its update never needs a value-preserving copy), and
the copy census tool (scripts/copy_audit.py). These tests pin the result
so a regression can never land silently: the budget numbers come from
the measured post-fix census (~29/step at this geometry, down from
137/step before the fixes — docs/perf_notes.md "Copy census") with
headroom for XLA version noise, NOT from aspiration.
"""
import importlib.util
import os

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.fluid as fluid
from paddle_tpu.testing import reset_programs

_spec = importlib.util.spec_from_file_location(
    "copy_audit",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 "scripts", "copy_audit.py"))
copy_audit = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(copy_audit)

# budget: measured post-fix per-step copy count is ~29 at this geometry
# (was 137 before the shared beta-pow + donation-floor fixes, a 4.3x
# reduction); 48 gives ~1.6x headroom for XLA scheduling noise without
# ever letting the per-param-pow regression (which would re-add ~108)
# back in
PER_STEP_COPY_BUDGET = 48


def _build_tiny_bert():
    from paddle_tpu.models import bert
    from paddle_tpu.distributed import fleet
    reset_programs(0)
    cfg = bert.BertConfig(vocab_size=256, hidden_size=16, num_layers=4,
                          num_heads=2, intermediate_size=32, max_position=32,
                          seq_len=8, hidden_dropout=0.0,
                          attention_dropout=0.0)
    ids, labels, loss = bert.build_pretrain_program(cfg)
    fleet.init(is_collective=True)
    strategy = fleet.DistributedStrategy()
    strategy.amp = True
    opt = fleet.distributed_optimizer(
        paddle.optimizer.Adam(learning_rate=1e-4), strategy)
    opt.minimize(loss)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    feed = {"input_ids": rng.randint(0, cfg.vocab_size,
                                     (4, 8)).astype(np.int64),
            "mlm_labels": rng.randint(0, cfg.vocab_size,
                                      (4, 8, 1)).astype(np.int64)}
    return exe, feed, loss


def test_copies_per_step_budget_and_donation_hygiene():
    """The k-step dispatch's loop body stays under the copies-per-step
    budget, the single-step entry has ZERO donated-param staging copies
    (the donation floor works), 100%% of found copies are classified, and
    the Adam program carries exactly ONE shared beta-pow pair."""
    exe, feed, loss = _build_tiny_bert()

    # structural: one shared pow pair, not 2-per-param
    gb = fluid.default_main_program().global_block()
    pow_vars = [n for n in gb.vars if "beta1_pow" in n or "beta2_pow" in n]
    assert sorted(pow_vars) == ["adam_beta1_pow_acc_0",
                                "adam_beta2_pow_acc_0"], pow_vars
    advances = [op for op in gb.ops
                if op.attrs.get("__adam_pow_advance__")]
    assert len(advances) == 2          # appended once, after the adam ops
    assert all(op is gb.ops[-3] or op is gb.ops[-2] or op is gb.ops[-1]
               for op in advances)

    # single-step program: the donation floor must leave no
    # entry-param-staging copies (each would be a per-run() copy op)
    txt1 = exe.compiled_hlo(feed, [loss])
    counts1, _bytes1, per_step1, total1 = copy_audit.copy_census(txt1)
    assert counts1.get("entry-param-staging", 0) == 0, dict(counts1)
    assert per_step1 == 0              # no training loop in this program
    assert sum(counts1.values()) == total1   # 100% classified

    # k-step dispatch: the loop body is the per-step cost on hardware
    txtk = exe.compiled_hlo(feed, [loss], k=4)
    countsk, _bytesk, per_stepk, totalk = copy_audit.copy_census(txtk)
    assert sum(countsk.values()) == totalk   # 100% classified
    assert per_stepk <= PER_STEP_COPY_BUDGET, (per_stepk, dict(countsk))


def test_legacy_per_param_pow_checkpoint_adopts_into_shared_pair():
    """Checkpoints written BEFORE the beta-pow sharing carry one
    `<param>_beta{1,2}_pow_acc_*` entry per param (all equal). Loading
    one must not silently restart bias correction at beta^1: the
    executor adopts the legacy value into the shared var and drops the
    stale copies (mirroring _ensure_stacked_params, beside it in
    Executor._resolve_call); disagreeing legacy
    entries are ambiguous and adopt nothing."""
    import jax.numpy as jnp
    from paddle_tpu.framework.scope import global_scope

    exe, feed, loss = _build_tiny_bert()
    scope = global_scope()
    # simulate an old-checkpoint load: per-param pows at beta^6, and a
    # stale shared value from startup (beta^1)
    legacy = jnp.asarray([0.9 ** 6], jnp.float32)
    scope.set("enc0_attn_qkv_w_beta1_pow_acc_0", legacy)
    scope.set("enc1_attn_qkv_w_beta1_pow_acc_0", legacy)
    exe.run(feed=feed, fetch_list=[loss])
    # adoption happened before the step: the step then advanced beta^6
    # once -> beta^7; the stale per-param entries are gone
    got = float(np.asarray(scope.find("adam_beta1_pow_acc_0"))[0])
    assert abs(got - 0.9 ** 7) < 1e-6, got
    assert scope.find("enc0_attn_qkv_w_beta1_pow_acc_0") is None

    # disagreeing legacy entries: ambiguous -> untouched
    exe, feed, loss = _build_tiny_bert()
    scope = global_scope()
    scope.set("enc0_attn_qkv_w_beta2_pow_acc_0",
              jnp.asarray([0.5], jnp.float32))
    scope.set("enc1_attn_qkv_w_beta2_pow_acc_0",
              jnp.asarray([0.25], jnp.float32))
    exe.run(feed=feed, fetch_list=[loss])
    got2 = float(np.asarray(scope.find("adam_beta2_pow_acc_0"))[0])
    assert abs(got2 - 0.999 ** 2) < 1e-6, got2      # startup value, advanced
    assert scope.find("enc0_attn_qkv_w_beta2_pow_acc_0") is not None


def test_copy_census_classifier_on_synthetic_hlo():
    """The classifier itself, no XLA compile: every copy kind lands in
    the right cause bucket and nothing is dropped."""
    txt = """\
HloModule jit_step, is_scheduled=true

%fused_computation.1 (p0: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  ROOT %copy.9 = f32[8,8]{1,0} copy(f32[8,8]{1,0} %p0)
}

%region_0.body (arg: (f32[1], s32[])) -> (f32[1], s32[]) {
  %arg = (f32[1]{0}, s32[]) parameter(0)
  %gte.0 = f32[1]{0} get-tuple-element((f32[1]{0}, s32[]) %arg), index=0
  %gte.1 = s32[] get-tuple-element((f32[1]{0}, s32[]) %arg), index=1
  %copy.1 = f32[1]{0} copy(f32[1]{0} %gte.0)
  %copy.2 = s32[] copy(s32[] %gte.1)
  %big = f32[4096]{0} broadcast(f32[1]{0} %gte.0), dimensions={}
  %copy.3 = f32[4096]{0} copy(f32[4096]{0} %big)
  ROOT %tup = (f32[1]{0}, s32[]) tuple(%copy.1, %copy.2)
}

ENTRY %main.10 (Arg_0.1: f32[4,4], Arg_1.2: f32[]) -> (f32[], f32[4,4]) {
  %Arg_0.1 = f32[4,4]{1,0} parameter(0)
  %Arg_1.2 = f32[] parameter(1)
  %copy.4 = f32[4,4]{1,0} copy(f32[4,4]{1,0} %Arg_0.1)
  %w = (f32[1]{0}, s32[]) while((f32[1]{0}, s32[]) %init), \
condition=%cond, body=%region_0.body
  %copy.5 = f32[] copy(f32[] %Arg_1.2)
  ROOT %tuple.1 = (f32[], f32[4,4]{1,0}) tuple(%copy.5, %copy.4)
}
"""
    counts, byte_tot, per_step, total = copy_audit.copy_census(txt)
    assert total == 6 and sum(counts.values()) == 6
    assert counts["fused-layout"] == 1
    assert counts["step-state-inplace"] == 1      # f32[1] in the loop body
    assert counts["rng-counter"] == 1             # the s32 loop counter
    assert counts["loop-activation"] == 1         # the f32[4096] body copy
    assert counts["entry-param-staging"] == 2     # both entry param copies
    assert per_step == 2                          # body f32 copies
    assert byte_tot["loop-activation"] == 4096 * 4
