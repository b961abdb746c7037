"""Reading collectives and op classes from a fixed optimized-HLO snippet."""
from bench_fixtures import import_harness

import_harness()
import hlo  # noqa: E402

SNIPPET = """\
HloModule jit_step, is_scheduled=true

%fused_computation.1 (param_0: f32[8,64], param_1: f32[64,64]) -> f32[8,64] {
  %param_0 = f32[8,64]{1,0} parameter(0)
  %param_1 = f32[64,64]{1,0} parameter(1)
  ROOT %convolution.3 = f32[8,64]{1,0} convolution(%param_0, %param_1), dim_labels=bf_io->bf
}

%add (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %add.1 = f32[] add(%x, %y)
}

%body.2 (arg: (s32[], f32[8,64])) -> (s32[], f32[8,64]) {
  %arg = (s32[], f32[8,64]{1,0}) parameter(0)
  %gte.1 = f32[8,64]{1,0} get-tuple-element(%arg), index=1
  %fusion.7 = f32[8,64]{1,0} fusion(%gte.1, %gte.1), kind=kOutput, calls=%fused_computation.1
  %all-reduce-start.2 = f32[8,64]{1,0} all-reduce-start(%fusion.7), replica_groups=[2,2]<=[4], to_apply=%add
  %all-reduce-done.2 = f32[8,64]{1,0} all-reduce-done(%all-reduce-start.2)
  %tanh.1 = f32[8,64]{1,0} tanh(%all-reduce-done.2)
  ROOT %tuple.1 = (s32[], f32[8,64]{1,0}) tuple(%gte.0, %tanh.1)
}

%cond.2 (arg: (s32[], f32[8,64])) -> pred[] {
  %constant.9 = s32[] constant(12)
  %gte.3 = s32[] get-tuple-element(%arg), index=0
  ROOT %lt = pred[] compare(%gte.3, %constant.9), direction=LT
}

ENTRY %main.9 (p0: f32[8,64], p1: bf16[1024]) -> f32[8,64] {
  %p0 = f32[8,64]{1,0} parameter(0)
  %p1 = bf16[1024]{0} parameter(1)
  %while.5 = (s32[], f32[8,64]{1,0}) while(%tuple.0), condition=%cond.2, body=%body.2, backend_config={"known_trip_count":{"n":"12"},"known_init_step":{"init":"0","step":"1"}}
  %all-gather.4 = bf16[4096]{0} all-gather(%p1), replica_groups=[1,4]<=[4], dimensions={0}
  ROOT %gte.9 = f32[8,64]{1,0} get-tuple-element(%while.5), index=1
}
"""


def test_collective_bytes_weight_loop_bodies_by_trip_count():
    # the all-reduce in the body runs 12 times: 12 * 8*64*4 bytes; the
    # all-gather once, counted at its (gathered) result shape: 4096*2 bytes
    assert hlo.collective_bytes(SNIPPET) == {"all-reduce": 12 * 8 * 64 * 4,
                                             "all-gather": 4096 * 2}


def test_op_classes_come_from_the_hlo():
    classes = hlo.Program(SNIPPET).op_classes()
    assert classes["fusion.7"] == "matmul"        # holds a convolution
    assert classes["convolution.3"] == "matmul"
    assert classes["all-reduce-start.2"] == "collective"
    assert classes["all-reduce-done.2"] == "collective"
    assert classes["all-gather.4"] == "collective"
    assert classes["tanh.1"] == "other"
    assert classes["while.5"] == "control"   # its event spans its body


def test_trip_counts_multiply_down_the_call_chain():
    trips = hlo.Program(SNIPPET).trip_counts()
    assert trips["body.2"] == 12
    assert trips["fused_computation.1"] == 12
    assert trips["main.9"] == 1


def test_trip_count_read_from_the_loop_condition_without_backend_config():
    # the TPU compiler writes no known_trip_count: the bound of the
    # condition's counter < N gives it
    tpu = SNIPPET.replace(
        ', backend_config={"known_trip_count":{"n":"12"},'
        '"known_init_step":{"init":"0","step":"1"}}', "")
    assert "known_trip_count" not in tpu
    assert hlo.Program(tpu).trip_counts()["body.2"] == 12
    assert hlo.collective_bytes(tpu)["all-reduce"] == 12 * 8 * 64 * 4
