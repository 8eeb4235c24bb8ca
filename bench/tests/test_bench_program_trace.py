"""The program's spans and scopes read out of a trace: ``serve.*`` host
spans kept apart from ``bench.*``, each device op's scope path from the
serialized trace, and the idle and cast shares read from them."""
import pytest

from bench import program_trace as pt
from bench import trace
from bench.trace import Event

BODY = "jit(decode)/while/body/"


def _synthetic():
    ops = [pt.Op("convert_bitcast_fusion", 0.0, 1.0, "fusion:kLoop",
                 BODY + "attn/qkv/cast/convert_element_type:"),
           pt.Op("convert", 0.0, 0.5, "convert", "", True),   # hoisted
           pt.Op("gemm", 1.0, 2.0, "custom-call:tpu_custom_call",
                 BODY + "attn/qkv/pallas_call"),
           pt.Op("while", 0.0, 4.0, "while", "jit(decode)/while"),
           pt.Op("fusion", 2.0, 4.0, "fusion:kLoop",
                 BODY + "moe/experts/cast/convert_element_type:"),
           pt.Op("gemm", 4.0, 5.0, "custom-call:tpu_custom_call",
                 BODY + "moe/experts/pallas_call"),
           pt.Op("argmax", 7.0, 7.5, "fusion:kLoop", "jit(_argmax)/argmax:"),
           pt.Op("argmax", 9.6, 9.7, "fusion:kLoop", "jit(_argmax)/argmax:"),
           pt.Op("copy", 9.8, 11.0, "copy", BODY + "lm_head/cast/transpose:")]
    program = [Event("serve.step", 0.0, 10.0),
               Event("serve.sample", 4.5, 6.0),
               Event("serve.sync", 5.8, 6.9),
               Event("serve.sample", 7.0, 7.8),
               Event("serve.sample", 9.0, 9.5),
               Event("serve.sample", 10.5, 11.0)]    # past the slice
    return pt.ProgramTrace(
        trace.Trace({"/device:TPU:0": ops}, [], (0.0, 10.0)), program)


def test_idle_gaps_fall_to_the_program_span_overlapping_them_most():
    p = _synthetic()
    assert trace.idle_gaps(p.trace) == [(5.0, 7.0), (7.5, 9.6), (9.7, 9.8)]
    # (5, 7) overlaps the first sample by 1.0 and the sync by 1.1; (7.5,
    # 9.6) overlaps two samples; (9.7, 9.8) no finer span than the step.
    assert pt.idle_by_program_span(p) == pytest.approx(
        {"serve.sync": 2.0, "serve.sample": 2.1, "serve.step": 0.1})
    assert pt.sample_idle_share(p) == pytest.approx(21.0)


def test_device_time_by_scope_component():
    by = pt.time_by_scope(_synthetic())
    # Loops left out; the copy is clipped to the slice's end.
    assert by[""] == pytest.approx(1 + 0.5 + 1 + 2 + 1 + 0.5 + 0.1 + 0.2)
    assert by == pytest.approx({"": 6.3, "jit(decode)": 5.2, "while": 5.2,
                                "body": 5.2, "attn": 2.0, "qkv": 2.0,
                                "cast": 3.2, "moe": 3.0, "experts": 3.0,
                                "lm_head": 0.2, "jit(_argmax)": 0.6})
    # The hoisted convert has no scope but counts as a weight cast.
    assert pt.cast_s(_synthetic()) == pytest.approx((3.2, 0.5))
    assert pt.weight_cast_share(_synthetic()) == pytest.approx(
        100 * 3.7 / 6.3)
    gemm = pt.gemm_by_scope(_synthetic())
    assert {c: gemm[c] for c in ("attn", "experts")} == pytest.approx(
        {"attn": 1.0, "experts": 1.0})
    assert "cast" not in gemm


def test_shares_are_none_without_the_program_s_marks():
    p = _synthetic()
    for op in p.trace.devices["/device:TPU:0"]:
        op.scope = op.scope.replace("/cast/", "/")
    p.program = [s for s in p.program if s.name != "serve.sample"]
    assert pt.sample_idle_share(p) is None
    assert pt.weight_cast_share(p) is None


def test_hoisted_weight_casts_by_their_hlo_text():
    hoisted = ("%convert.70 = bf16[28,2048,6144]{2,1,0:T(8,128)(2,1)} "
               "convert(f32[28,2048,6144]{2,1,0:T(8,128)} "
               "%params__layers____mlp____w_gate__.1)")
    table = ("%copy.19 = bf16[151936,2048]{0,1:T(8,128)(2,1)} copy(f32["
             "151936,2048]{1,0:T(8,128)} %params__embed__.1)")
    kv = ("%convert.20 = f32[2560,16,8,128]{3,2,1,0:T(8,128)} convert("
          "bf16[2560,16,8,128]{3,2,1,0:T(8,128)(2,1)} %fusion.2)")
    relayout = ("%copy.20 = bf16[151936,2048]{1,0:T(8,128)(2,1)} copy("
                "bf16[151936,2048]{0,1:T(8,128)(2,1)} %copy.19)")
    assert [bool(pt._PARAM_CAST.search(t))
            for t in (hoisted, table, kv, relayout)] == [
        True, True, False, False]


_XSPACE = r'''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 4000000 duration_ps: 500000 } }
  event_metadata { key: 1 value { id: 1
    name: "%convert_bitcast_fusion.6 = bf16[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%f"
    display_name: "convert_bitcast_fusion.6"
    stats { metadata_id: 7 str_value: "jit(d)/while/body/attn/qkv/cast/convert_element_type:" } } }
  event_metadata { key: 2 value { id: 2
    name: "%gemm.1 = bf16[8]{0} custom-call(bf16[8]{0} %a), custom_call_target=\"tpu_custom_call\""
    stats { metadata_id: 8 int64_value: 3 }
    stats { metadata_id: 7 ref_value: 9 } } }
  event_metadata { key: 3 value { id: 3 name: "%copy.2 = bf16[8]{0} copy(bf16[8]{0} %a)" } }
  stat_metadata { key: 7 value { id: 7 name: "tf_op" } }
  stat_metadata { key: 8 value { id: 8 name: "flops" } }
  stat_metadata { key: 9 value { id: 9 name: "jit(d)/while/body/mlp/pallas_call" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 6000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 5000000
             stats { metadata_id: 3 int64_value: 4 } }
    events { metadata_id: 3 offset_ps: 2000000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 2000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.slice" } }
  event_metadata { key: 2 value { id: 2 name: "serve.step" } }
  event_metadata { key: 3 value { id: 3 name: "serve.sample" } }
  event_metadata { key: 4 value { id: 4 name: "bench.sample" } }
  stat_metadata { key: 3 value { id: 3 name: "active" } }
}
'''


def test_from_a_serialized_trace():
    from jax.profiler import ProfileData
    raw = ProfileData.text_proto_to_serialized_xspace(_XSPACE)
    scopes = pt.op_scopes(raw)
    assert sorted(scopes.values()) == [
        "jit(d)/while/body/attn/qkv/cast/convert_element_type:",
        "jit(d)/while/body/mlp/pallas_call"]
    p = pt.from_profile(ProfileData.from_serialized_xspace(raw), scopes)
    # serve.* spans go to the program's list, bench.* where they always
    # went: trace.py's readers see what they saw before.
    assert [s.name for s in p.program] == ["serve.step", "serve.sample"]
    assert sorted(s.name for s in p.trace.spans) == ["bench.sample",
                                                     "bench.slice"]
    assert p.trace.slice == pytest.approx((1e-6, 7e-6))
    ops = p.trace.devices["/device:TPU:0"]
    assert [(o.name, o.category) for o in ops] == [
        ("convert_bitcast_fusion", "fusion:kLoop"),
        ("gemm", "custom-call:tpu_custom_call"), ("copy", "copy")]
    assert [pt.components(o.scope)[-1:] for o in ops] == [
        ["cast"], ["mlp"], []]
    # The gap (3, 4) us after the convert falls to serve.sample, the gap
    # (5.5, 7) us to no program span (serve.step ends at 6 us).
    assert pt.idle_by_program_span(p) == pytest.approx(
        {"serve.sample": 1e-6, "serve.step": 1.5e-6})
    assert pt.weight_cast_share(p) == pytest.approx(100 * 2 / 3.5)


def test_slice_falls_back_to_the_engine_steps():
    from jax.profiler import ProfileData
    text = _XSPACE.replace('name: "bench.slice"', 'name: "other"')
    raw = ProfileData.text_proto_to_serialized_xspace(text)
    q = pt.from_profile(ProfileData.from_serialized_xspace(raw),
                        pt.op_scopes(raw))
    assert q.trace.slice == pytest.approx((1e-6, 6e-6))


def _recorded():
    """100 ms of a traced gen-batch run on the chip (``tests/data``), with
    the program's spans and each op's scope path."""
    import json
    import pathlib
    raw = json.loads((pathlib.Path(__file__).parent / "data"
                      / "mixtral-gen-batch-trace.json").read_text())
    us = 1e-6
    ops = [pt.Op(raw["op_names"][n], s * us, (s + d) * us,
                 raw["op_kinds"][k], raw["scopes"][sc], bool(cast))
           for n, k, sc, cast, s, d in raw["ops"]]
    spans = [Event(n, s * us, (s + d) * us) for n, s, d in raw["spans"]]
    program = [Event(n, s * us, (s + d) * us) for n, s, d in raw["program"]]
    return pt.ProgramTrace(trace.Trace({"/device:TPU:0": ops}, spans,
                                       (0.0, raw["length_us"] * us)), program)


def test_recorded_trace_reduces_to_its_layers():
    p = _recorded()
    assert trace.busy_s(p.trace) == pytest.approx(0.045821649, rel=1e-6)
    assert pt.sample_idle_share(p) == pytest.approx(47.893317, rel=1e-6)
    assert pt.cast_s(p) == pytest.approx((0.026712244, 0.002358613),
                                         rel=1e-6)
    assert pt.weight_cast_share(p) == pytest.approx(63.443498, rel=1e-6)
    by = pt.time_by_scope(p)
    assert by["experts"] == pytest.approx(0.033368432, rel=1e-6)
    # The harness's own attribution of the same gaps agrees: the serve.*
    # spans sit inside the bench.* ones.
    bench = dict(trace.idle_by_span(p.trace))["bench.sample"]
    assert pt.idle_by_program_span(p)["serve.sample"] == pytest.approx(
        bench, rel=0.1)
    ops = p.trace.devices["/device:TPU:0"]
    # The expert GEMMs are the ragged kernels, scoped moe/experts; the
    # scoped casts are the experts' convert fusions and the table's copy;
    # the hoisted ones are layout copies that carry only the parameter's
    # name (the embedding table and the router).
    assert {o.name for o in ops if trace.is_gemm(o)
            and "experts" in pt.components(o.scope)} == {
        "ragged_gemm_swiglu", "ragged_gemm"}
    assert {o.name for o in ops if "cast" in pt.components(o.scope)} == {
        "convert_bitcast_fusion", "copy"}
    assert {(o.name, o.scope) for o in ops if o.param_cast and
            "cast" not in pt.components(o.scope)} == {
        ("copy", "params['embed']:"),
        ("copy", "params['layers']['moe']['router']:")}
