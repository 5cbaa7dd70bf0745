"""The trace reduction, on HLO text written here and on a trace that the
test records on the CPU (where host events with an ``hlo_op`` stat stand
in for a chip's device ops)."""

from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from bench import trace_reduce as tr  # noqa: E402

HLO = """\
HloModule jit_job, entry_computation_layout={(s32[64]{0})->s32[8]{0}}

%fused_computation (param_0: f32[64,8]) -> f32[8] {
  %param_0 = f32[64,8]{1,0} parameter(0)
  %c = f32[64]{0} constant({...})
  ROOT %dot.1 = f32[8]{0} dot(f32[64]{0} %c, f32[64,8]{1,0} %param_0), lhs_contracting_dims={0}, rhs_contracting_dims={0}
}

%fused_onehot (param_0: s32[64]) -> s32[8] {
  %param_0 = s32[64]{0} parameter(0)
  %iota.1 = s32[8,64]{1,0} iota(), iota_dimension=0
  %eq.1 = pred[8,64]{1,0} compare(%broadcast.1, %iota.1), direction=EQ, metadata={op_name="jit(f)/eq"}
  %convert.1 = s32[8,64]{1,0} convert(%eq.1)
  ROOT %reduce.1 = s32[8]{0} reduce(%convert.1, %zero), dimensions={1}, to_apply=%add, metadata={op_name="jit(f)/while/body/nk,nd->kd/dot_general"}
}

%sort_body (a: s32[], b: s32[]) -> pred[] {
  %a = s32[] parameter(0)
  %b = s32[] parameter(1)
  ROOT %lt = pred[] compare(s32[] %a, s32[] %b), direction=LT
}

ENTRY %main (p: s32[64]) -> s32[8] {
  %p = s32[64]{0} parameter(0)
  %fusion.3 = f32[8]{0} fusion(f32[64,8]{1,0} %x), kind=kOutput, calls=%fused_computation
  %convert_reduce_fusion.2 = s32[8]{0} fusion(s32[64]{0} %p), kind=kLoop, calls=%fused_onehot
  %reduce.9 = s32[8]{0} reduce(%convert.1, %zero), dimensions={1}, to_apply=%add, metadata={op_name="jit(f)/reduce_sum"}
  %sort.2 = s32[64]{0} sort(s32[64]{0} %p), dimensions={0}, to_apply=%sort_body
  %all-to-all-start = (s32[64]{0}, s32[64]{0}) all-to-all-start(s32[64]{0} %p)
  %while.1 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t), condition=%cond, body=%body
  %custom-call.4 = f32[8,128]{1,0} custom-call(f32[8,128]{1,0} %q), custom_call_target="tpu_custom_call", backend_config={"name": "_scatter_kernel"}
  ROOT %copy.5 = s32[8]{0} copy(s32[8]{0} %y)
}
"""


def test_parse_hlo_classifies_by_what_runs_inside():
    table = tr.parse_hlo([HLO]).classes["jit_job"]
    assert table["fusion.3"] == "contraction"
    # a one-hot product that XLA rewrote to a compare and a reduce is still
    # the dot_general the program wrote; a plain reduce is not
    assert table["convert_reduce_fusion.2"] == "contraction"
    assert table["reduce.9"] == "reduce"
    assert table["sort.2"] == "sort"
    assert table["all-to-all-start"] == "collective"
    assert table["while.1"] == "control"
    assert table["custom-call.4"] == "sort"
    assert table["copy.5"] == "copy"


def test_tpu_event_names_are_hlo_text():
    programs = tr.parse_hlo([HLO])
    table = programs.classes["jit_job"]
    event = ("%fusion.3 = f32[8]{0:T(512)} fusion(f32[64,8]{1,0} %x), "
             "kind=kOutput, calls=%fused_computation")
    assert tr.classify_event(event, table, programs) == ("fusion.3",
                                                        "contraction")
    other = ("%fusion.9 = f32[8]{0} fusion(f32[64,8]{1,0} %x), kind=kLoop, "
             "calls=%fused_computation")
    assert tr.classify_event(other, {}, programs) == ("fusion.9",
                                                     "contraction")
    assert tr.classify_event("%sort.7 = s32[4]{0} sort(s32[4]{0} %p)",
                             {}, programs) == ("sort.7", "sort")
    assert tr.classify_event("copy.2", {}, programs) == ("copy.2", "copy")


def test_union_merges_overlaps():
    s, e = tr.union(np.array([5.0, 0.0, 2.0, 10.0]),
                    np.array([6.0, 3.0, 4.0, 11.0]))
    assert s.tolist() == [0.0, 5.0, 10.0] and e.tolist() == [4.0, 6.0, 11.0]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x.T).sum() + jnp.sort(x[0]).sum())
    x = jnp.ones((512, 512))
    f(x).block_until_ready()
    d = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(d))
    with jax.profiler.TraceAnnotation("bench.job.call"):
        y = f(x)
    with jax.profiler.TraceAnnotation("bench.job.fetch"):
        y.block_until_ready()
    jax.profiler.stop_trace()
    return tr.find_xplane(d), f.lower(x).compile().as_text()


def test_recorded_trace_reduces(recorded):
    path, text = recorded
    s = tr.summarize(path, [text])
    assert s.n_events > 0 and 0 < s.busy_s <= s.window_s
    assert s.class_ms("contraction") > 0
    assert s.class_ms("sort") > 0
    assert 0 <= s.idle_pct() < 100
    ops = s.top_ops(10)
    assert ops and all(isinstance(v, float) for _, v in ops)
    labels = {label for label, _ in s.top_gaps(10)}
    assert labels <= {"bench.job.call", "bench.job.fetch", "no bench span"}


def test_no_device_op_reads_nothing(recorded, tmp_path):
    import jax

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.idle"):
        pass
    jax.profiler.stop_trace()
    s = tr.summarize(tr.find_xplane(tmp_path))
    assert s.class_ms("sort") is None and s.idle_pct() is None
