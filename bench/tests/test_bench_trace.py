"""Trace reduction: busy time from the union of op intervals, GEMM time by
HLO category, idle gaps put down to the host span they fall in."""
import pytest

from bench import trace
from bench.trace import Event, Trace


def _trace():
    ops = [Event("fusion", 0.0, 1.0, "fusion:kLoop"),
           Event("gemm", 0.5, 2.0, "custom-call:tpu_custom_call"),
           Event("convolution", 3.0, 4.0, "convolution"),
           Event("convolution_add_fusion", 6.0, 9.0, "fusion:kOutput"),
           Event("copy", 9.5, 12.0, "copy")]                   # past slice
    spans = [Event("bench.slice", 0.0, 10.0),
             Event("bench.step", 0.0, 5.0),
             Event("bench.decode", 0.0, 2.0),
             Event("bench.sample", 2.0, 2.9),
             Event("bench.step", 5.0, 10.0),
             Event("bench.prefill", 6.0, 9.0),
             Event("bench.wait", 9.2, 10.0)]
    return Trace({"/device:TPU:0": ops}, spans, (0.0, 10.0))


def test_busy_is_the_union_clipped_to_the_slice():
    t = _trace()
    assert t.window_s == 10.0
    assert trace.busy_s(t) == pytest.approx(2.0 + 1.0 + 3.0 + 0.5)


def test_gemm_time_by_category():
    assert trace.gemm_s(_trace()) == pytest.approx(1.5 + 1.0 + 3.0)
    assert not trace.is_gemm(Event("fusion", 0, 1, "fusion:kLoop"))


def test_idle_gaps_and_their_host_spans():
    t = _trace()
    assert trace.idle_gaps(t) == [(2.0, 3.0), (4.0, 6.0), (9.0, 9.5)]
    by = dict(trace.idle_by_span(t))
    # A gap goes whole to the inner span that overlaps it most; (4, 6)
    # overlaps no inner span, so it falls to a step.
    assert by == pytest.approx({"bench.sample": 1.0, "bench.step": 2.0,
                                "bench.wait": 0.5})
    assert sum(by.values()) == pytest.approx(10.0 - trace.busy_s(t))


def test_top_ops_largest_first():
    top = trace.top_ops(_trace(), n=2)
    assert [name for name, _ in top] == [
        "convolution_add_fusion [fusion:kOutput]",
        "gemm [custom-call:tpu_custom_call]"]


def _recorded():
    """100 ms of a traced chat run on the chip (``tests/data``)."""
    import json
    import pathlib
    raw = json.loads((pathlib.Path(__file__).parent / "data"
                      / "qwen3-chat-trace.json").read_text())
    us = 1e-6
    ops = [Event(raw["op_names"][n], s * us, (s + d) * us, raw["op_kinds"][k])
           for n, k, s, d in raw["ops"]]
    spans = [Event(n, s * us, (s + d) * us) for n, s, d in raw["spans"]]
    return Trace({"/device:TPU:0": ops}, spans, (0.0, raw["length_us"] * us))


def test_recorded_trace_reduces_to_its_parts():
    t = _recorded()
    busy, gemm = trace.busy_s(t), trace.gemm_s(t)
    assert busy == pytest.approx(0.073941919, rel=1e-6)
    assert gemm == pytest.approx(0.023482324, rel=1e-6)
    # The layer scan's loop holds the GEMMs: busy, but not an op of its own.
    assert any(e.category == "while" for e in t.devices["/device:TPU:0"])
    assert all("while" not in name for name, _ in trace.top_ops(t))
    kinds = {e.category for e in t.devices["/device:TPU:0"] if trace.is_gemm(e)}
    assert kinds == {"custom-call:tpu_custom_call"}
    by = dict(trace.idle_by_span(t))
    assert sum(by.values()) == pytest.approx(t.window_s - busy)
    # The slots' host round trips leave the device idle most.
    assert max(by, key=by.get) == "bench.sample"


def test_op_kind_and_name_from_hlo_text():
    gemm = ('%gemm.58 = bf16[8,1024]{1,0:T(8,128)(2,1)S(1)} custom-call('
            'bf16[8,2048]{1,0} %fusion.89), custom_call_target='
            '"tpu_custom_call", x')
    loop = ('%while.13 = (s32[]{:T(128)}, bf16[8,1,2048]{2,0,1}) while(('
            's32[]{:T(128)}) %tuple.57), condition=%a')
    fusion = ('%fusion.2 = bf16[8,2048]{1,0:T(8,128)(2,1)} fusion(bf16[8]'
              ' %copy.20), kind=kCustom, calls=%f')
    assert (trace.op_name(gemm), trace.op_kind(gemm)) == (
        "gemm", "custom-call:tpu_custom_call")
    assert (trace.op_name(loop), trace.op_kind(loop)) == ("while", "while")
    assert trace.op_kind(fusion) == "fusion:kCustom"
