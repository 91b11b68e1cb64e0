"""Static HLO parsing (chiaswarm_tpu/obs/hlocost.py): conv/dot/flash
FLOPs and HBM byte estimates from scheduled-HLO text (operands printed
as bare %names, shapes resolved through the definition map) and
``ProgramCapture`` — costed from canned fixtures, no TPU needed — and
the compiled-side contract audits of ``analysis/hlocheck.py``."""

from chiaswarm_tpu.obs import hlocost


_HLO = """\
HloModule jit_fn, is_scheduled=true

%fused_computation.7 (param_0.1: bf16[2,64,64,320], param_1.2: bf16[3,3,320,640]) -> bf16[2,64,64,640] {
  %param_0.1 = bf16[2,64,64,320]{3,2,1,0:T(8,128)(2,1)} parameter(0)
  %param_1.2 = bf16[3,3,320,640]{3,2,1,0:T(8,128)(2,1)} parameter(1)
  ROOT %convolution.9 = bf16[2,64,64,640]{3,2,1,0:T(8,128)(2,1)} convolution(%param_0.1, %param_1.2), window={size=3x3 pad=1_1x1_1}, dim_labels=b01f_01io->b01f
}

%fused_computation.8 (p0: bf16[2,4096,640], p1: bf16[640,640]) -> bf16[2,4096,640] {
  %p0 = bf16[2,4096,640]{2,1,0:T(8,128)(2,1)} parameter(0)
  %p1 = bf16[640,640]{1,0:T(8,128)(2,1)} parameter(1)
  ROOT %dot.3 = bf16[2,4096,640]{2,1,0:T(8,128)(2,1)} dot(%p0, %p1), lhs_batch_dims={}, lhs_contracting_dims={2}, rhs_contracting_dims={0}
}

ENTRY %main (a: bf16[2,64,64,320], w: bf16[3,3,320,640]) -> bf16[2,64,64,640] {
  %a = bf16[2,64,64,320]{3,2,1,0:T(8,128)(2,1)} parameter(0)
  %w = bf16[3,3,320,640]{3,2,1,0:T(8,128)(2,1)} parameter(1)
  %pad.1 = f32[8,4096,128]{2,1,0:T(8,128)} parameter(2)
  %conv_fusion.1 = bf16[2,64,64,640]{3,2,1,0:T(8,128)(2,1)} fusion(%a, %w), kind=kOutput, calls=%fused_computation.7
  %x = bf16[2,4096,640]{2,1,0:T(8,128)(2,1)} parameter(3)
  %m = bf16[640,640]{1,0:T(8,128)(2,1)} parameter(4)
  %dot_fusion.2 = bf16[2,4096,640]{2,1,0:T(8,128)(2,1)} fusion(%x, %m), kind=kOutput, calls=%fused_computation.8
  %flash_attention = f32[8,4096,128]{2,1,0:T(8,128)S(1)} custom-call(%pad.1, %pad.1, %pad.1), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[8,4096,128]{2,1,0}, f32[8,4096,128]{2,1,0}, f32[8,4096,128]{2,1,0}}
  ROOT %out = bf16[2,64,64,640]{3,2,1,0:T(8,128)(2,1)} fusion(%conv_fusion.1), kind=kLoop, calls=%fused_computation.7
}
"""

def test_conv_fusion_flops_and_bytes():
    costs = hlocost.parse_hlo_text(_HLO)
    conv = costs["conv_fusion.1"]
    # 2 * out_elems * window * Cin = 2 * (2*64*64*640) * 9 * 320
    assert conv["flops"] == 2 * (2 * 64 * 64 * 640) * 9 * 320
    assert conv["kind"] == "conv"
    # bytes: result + a + w, bf16
    expect = 2 * (2 * 64 * 64 * 640 + 2 * 64 * 64 * 320 + 3 * 3 * 320 * 640)
    assert conv["bytes"] == expect
    assert conv["computation"] == "main"


def test_dot_fusion_flops():
    costs = hlocost.parse_hlo_text(_HLO)
    dot = costs["dot_fusion.2"]
    # 2 * out_elems * K = 2 * (2*4096*640) * 640
    assert dot["flops"] == 2 * (2 * 4096 * 640) * 640
    assert dot["kind"] == "dot"


def test_flash_custom_call_flops():
    costs = hlocost.parse_hlo_text(_HLO)
    fl = costs["flash_attention"]
    # 4 * BH * L * S * D from the folded (B*H, L_pad, D) operands
    assert fl["flops"] == 4 * 8 * 4096 * 4096 * 128
    assert fl["kind"] == "flash"
    # bytes resolve through the definition map (operands are bare %names):
    # f32 result + three f32 operands
    assert fl["bytes"] == 4 * (8 * 4096 * 128) * 4


def test_operand_scan_stops_at_list_close():
    shapes = hlocost.operand_shapes(
        "  %f = bf16[4,4]{1,0:T(8,128)(2,1)} fusion(%a, %b), kind=kLoop, "
        "calls=%c", "fusion",
        {"a": ("bf16", [4, 4]), "b": ("f32", [2, 2]),
         "c": ("f32", [9, 9])})
    assert shapes == [("bf16", [4, 4]), ("f32", [2, 2])]


def test_program_capture_keys_by_signature():
    """ProgramCapture recompiles per input-shape signature (a lattice
    program reused across widths must not call a stale executable)."""
    import jax.numpy as jnp

    cap = hlocost.ProgramCapture()
    wrapped = cap.capturing_toplevel_jit(lambda x: x * 2)
    a = wrapped(jnp.ones((2, 2)))
    b = wrapped(jnp.ones((2, 2)))
    assert len(cap.executables) == 1  # same signature: one compile
    c = wrapped(jnp.ones((4, 4)))
    assert len(cap.executables) == 2  # new signature: fresh compile
    assert a.shape == b.shape == (2, 2) and c.shape == (4, 4)
    hlo = cap.largest_hlo()
    assert hlo and "HloModule" in hlo


# ------------------- swarmproof compiled-side contracts (ISSUE 15):
# analysis/hlocheck.py audits lowered programs against declared
# collective/dtype/donation contracts — same canned-fixture stance,
# no jax needed.

from chiaswarm_tpu.analysis import hlocheck


_HLO_RING = """\
HloModule jit_ring, input_output_alias={ {}: (0, {}, may-alias), {1}: (2, {}) }, is_scheduled=true

ENTRY %main (q: f32[2,8,128], k: f32[2,8,128], v: f32[2,8,128]) -> f32[2,8,128] {
  %q = f32[2,8,128]{2,1,0} parameter(0)
  %k = f32[2,8,128]{2,1,0} parameter(1)
  %v = f32[2,8,128]{2,1,0} parameter(2)
  %cp.1 = f32[2,8,128]{2,1,0} collective-permute(%k), channel_id=1, source_target_pairs={{0,1},{1,2},{2,3},{3,0}}
  %cp-start.2 = f32[2,8,128]{2,1,0} collective-permute-start(%v), channel_id=2, source_target_pairs={{0,1},{1,2},{2,3},{3,0}}
  %cp-done.2 = f32[2,8,128]{2,1,0} collective-permute-done(%cp-start.2)
  %scores = f32[2,8,8]{2,1,0} dot(%q, %cp.1), lhs_contracting_dims={2}, rhs_contracting_dims={2}
  %mixed = bf16[2,8,8]{2,1,0} dot(%q, %q), lhs_contracting_dims={2}, rhs_contracting_dims={2}
  %ar.3 = f32[2,8,8]{2,1,0} all-reduce(%scores), channel_id=3, replica_groups={{0,1,2,3}}, to_apply=%add
  %ag-start.4 = f32[2,8,128]{2,1,0} all-gather-start(%q), channel_id=4, replica_groups=[2,4]<=[8], dimensions={1}
  %ag-done.4 = f32[2,8,128]{2,1,0} all-gather-done(%ag-start.4)
  ROOT %out = f32[2,8,128]{2,1,0} dot(%ar.3, %cp-done.2), lhs_contracting_dims={2}, rhs_contracting_dims={1}
}
"""


def test_collective_census_counts_async_once_with_group_sizes():
    obs = hlocheck.collective_census(_HLO_RING)
    # the sync cp counts once, the -start/-done pair once more; the
    # -done halves never double-count
    assert obs["collective-permute"]["count"] == 2
    assert obs["all-reduce"]["count"] == 1
    assert obs["all-reduce"]["group_sizes"] == [4]   # {{0,1,2,3}}
    assert obs["all-gather"]["count"] == 1
    assert obs["all-gather"]["group_sizes"] == [4]   # [2,4]<=[8] iota
    assert "all-to-all" not in obs


def test_matmul_dtype_census_and_donated_params():
    assert hlocheck.matmul_dtype_census(_HLO_RING) == {"f32": 2,
                                                      "bf16": 1}
    # the alias table names params 0 and 2; 1 was dropped by XLA
    assert hlocheck.donated_param_indices(_HLO_RING) == [0, 2]
    assert hlocheck.donated_param_indices(_HLO) == []


def test_audit_flags_unexpected_collective():
    """A single-chip contract (max_total 0) catches ANY lowered
    collective — the compiler-surprise face of R11."""
    violations = hlocheck.audit_hlo(_HLO_RING,
                                    {"collectives": {"max_total": 0}},
                                    program="solo")
    assert len(violations) == 1
    v = violations[0]
    assert v["check"] == "collective-budget"
    assert v["rule"] == "replicated-psum" and v["program"] == "solo"
    assert "4 collective(s)" in v["message"]


def test_audit_per_op_min_max_bounds():
    contract = {"collectives": {
        "collective-permute": {"min": 3},   # ring didn't lower enough
        "all-reduce": {"max": 0},           # the r06 smoking gun
    }}
    msgs = [v["message"]
            for v in hlocheck.audit_hlo(_HLO_RING, contract)]
    assert len(msgs) == 2
    assert any("only 2 collective-permute(s)" in m for m in msgs)
    assert any("1 all-reduce(s)" in m for m in msgs)


def test_audit_dtype_drift():
    violations = hlocheck.audit_hlo(
        _HLO_RING, {"dtype": {"forbid": ["f32"], "allow_ops": 1}})
    assert len(violations) == 1
    assert violations[0]["rule"] == "dtype-drift"
    assert "2 f32" in violations[0]["message"]
    # within the allowance: silent
    assert hlocheck.audit_hlo(
        _HLO_RING, {"dtype": {"forbid": ["f32"], "allow_ops": 2}}) == []


def test_audit_donation_drop_is_r13s_compiled_face():
    violations = hlocheck.audit_hlo(
        _HLO_RING, {"donation": {"require_params": [0, 1, 2]}})
    assert len(violations) == 1
    assert violations[0]["rule"] == "donation-drift"
    assert "[1]" in violations[0]["message"]
    assert hlocheck.audit_hlo(
        _HLO_RING, {"donation": {"require_params": [0, 2]}}) == []


def test_audit_programs_reports_census_and_unknown_is_record_only():
    report = hlocheck.audit_programs(
        {"ring": _HLO_RING, "mystery": _HLO},
        {"programs": {"ring": {"collectives": {"all-reduce": {"max": 0}}}}})
    assert not report["ok"]
    assert [v["program"] for v in report["violations"]] == ["ring"]
    # census is recorded for every program, contracted or not
    assert report["programs"]["mystery"]["collectives"] == {}
    assert report["programs"]["ring"]["donated_params"] == [0, 2]
