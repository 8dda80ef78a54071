"""The reduction from a profiler trace to numbers: the device's busy union,
a kernel's time by its own name inside a program, the operations that took
most time, and idle gaps charged to the harness span that covers them. A
hand-made trace checks the arithmetic; the trace recorded on the chip
checks the names the reduction looks for."""
import json
import os

import pytest

from bench.profile import Trace, module_name, op_name

HERE = os.path.dirname(os.path.abspath(__file__))

# ns: a decode tick [100, 1100) whose `while` holds a grouped GEMM, a fusion
# that reads the GEMM's output (and names it among its operands) and the
# attention kernel; a copy and a prefill after it; the host's spans.
HAND = {
    "ops": [("while.1", 100, 1000), ("_gmm_swiglu.3", 150, 200),
            ("fusion.5", 400, 100), ("_paged_attn_decode.2", 600, 300),
            ("copy.1", 1200, 100)],
    "modules": [("jit__decode_step(123)", 100, 1000),
                ("jit_prefill(9)", 1150, 200)],
    "host": [("bench.window", 0, 2000), ("step", 50, 1400),
             ("observe", 1450, 100), ("wait", 1560, 400)],
}


def test_busy_union_and_window():
    tr = Trace.from_dict(HAND)
    assert tr.busy_intervals() == [[100, 1100], [1200, 1300]]
    assert tr.busy_s() == pytest.approx(1100e-9)
    assert tr.window_s() == pytest.approx(2000e-9)


def test_kernel_time_by_name_inside_its_program():
    tr = Trace.from_dict(HAND)
    assert tr.op_seconds(r"gmm", "_decode_step") == pytest.approx(200e-9)
    assert tr.op_seconds(r"paged_attn_decode", "_decode_step") == \
        pytest.approx(300e-9)
    assert tr.op_seconds(r"copy", "_decode_step") == 0.0
    assert tr.op_seconds(r"copy") == pytest.approx(100e-9)
    assert [e[0] for e in tr.module_events("_decode_step")] == \
        ["jit__decode_step(123)"]


def test_top_ops_charge_holders_their_own_time():
    got = dict(Trace.from_dict(HAND).top_ops())
    assert got == pytest.approx({"while": 400e-9, "_paged_attn_decode": 300e-9,
                                 "_gmm_swiglu": 200e-9, "fusion": 100e-9,
                                 "copy": 100e-9})


def test_idle_gaps_go_to_the_covering_span():
    got = dict(Trace.from_dict(HAND).idle_gaps())
    # [0,100) and [1100,1200) lie under `step`; [1300,2000) mostly `wait`
    assert got == pytest.approx({"wait": 700e-9, "step": 200e-9})


def test_names():
    assert op_name("%fusion.233 = f32[16,2048]{1,0} fusion(f32[8320,2048] "
                   "%_gmm_scaled.15)") == "fusion.233"
    assert op_name("_gmm_swiglu.15") == "_gmm_swiglu.15"
    assert module_name("jit__decode_step(9638999460649935486)") == \
        "_decode_step"
    assert module_name("jit_prefill_chunk(1)") == "prefill_chunk"


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "trace_excerpt.json")) as f:
        return Trace.from_dict(json.load(f))


def test_recorded_trace_names_what_the_readers_look_for(recorded):
    """A decode tick of granite.chat as traced on a TPU v5e: the readers'
    program and kernel names are there, each kernel inside its program."""
    progs = {module_name(n) for n, _, _ in recorded.modules}
    assert "_decode_step" in progs
    for pattern in (r"gmm", r"paged_attn_decode"):
        assert recorded.op_seconds(pattern, "_decode_step") > 0
    busy, window = recorded.busy_s(), recorded.window_s()
    assert 0 < busy <= window
    assert sum(s for _, s in recorded.top_ops(10 ** 6)) == \
        pytest.approx(busy, rel=1e-6)


# ns: a decode tick [100, 1100) of a program over four chips, its collectives
# (an all-gather in two halves, an all-reduce) beside its kernels; a
# prefill holding another all-gather
COLLECTIVES = {
    "ops": [("while.1", 100, 1000), ("all-gather-start.1", 120, 10),
            ("all-gather-done.1", 130, 300), ("_gmm_swiglu.2", 430, 200),
            ("all-reduce.3", 640, 100), ("fusion.4", 740, 200),
            ("async-collective-done.6", 940, 100),
            ("all-gather.5", 1200, 50)],
    "modules": [("jit__decode_step(7)", 100, 1000),
                ("jit_prefill(8)", 1150, 200)],
    "host": [("bench.window", 0, 2000)],
}


def test_collectives_share_of_the_decode_programs():
    from types import SimpleNamespace

    from bench.spec import reader
    read = reader("collectives.decode")
    run = SimpleNamespace(trace=Trace.from_dict(COLLECTIVES))
    assert read(run) == pytest.approx(100 * (10 + 300 + 100 + 100) / 1000)
    no_decode = dict(COLLECTIVES, modules=[("jit_prefill(8)", 1150, 200)])
    assert read(SimpleNamespace(trace=Trace.from_dict(no_decode))) is None
    assert read(SimpleNamespace(trace=None)) is None


def test_recorded_one_chip_tick_holds_no_collectives(recorded):
    from types import SimpleNamespace

    from bench.spec import reader
    assert reader("collectives.decode")(SimpleNamespace(trace=recorded)) == 0


def test_each_chips_busy_time():
    tr = Trace.from_dict(HAND)
    assert tr.chip_busy_s() == [tr.busy_s()]
    tr.chips = [tr.ops, [("fusion.1", 0, 100), ("fusion.2", 50, 100),
                         ("copy.3", 1900, 200)]]
    assert tr.chip_busy_s() == pytest.approx([1100e-9, 250e-9])


# three Mosaic kernels of a decode tick compiled over a 1x4 mesh for a
# described v5e:2x2 (operands and configs cut short), and a one-chip one
MESH_HLO = """\
  %shard_map.442 = f32[16,16,128]{2,1,0:T(8,128)S(1)} custom-call(%p.1), \
custom_call_target="tpu_custom_call", metadata={op_name="jit(_decode_step)/\
while/body/closed_call/attn/jit(_paged_attn_decode)/shard_map/pallas_call" \
stack_frame_id=59}, backend_config={"flag_configs":[]}
  %shard_map.443 = bf16[8320,1408]{1,0:T(8,128)(2,1)} custom-call(%p.2), \
custom_call_target="tpu_custom_call", metadata={op_name="jit(_decode_step)/\
while/body/closed_call/moe/experts/jit(_gmm_swiglu)/shard_map/pallas_call" \
stack_frame_id=157}, backend_config={"flag_configs":[]}
  %all-gather.92 = bf16[64,2048,1408]{2,1,0} all-gather(%p.3), \
metadata={op_name="jit(_decode_step)/while/body/moe/experts/jit(_gmm_swiglu)\
/shard_map"}
  ROOT %_gmm_scaled.15 = f32[8320,2048]{1,0} custom-call(%p.4), \
custom_call_target="tpu_custom_call", metadata={op_name="jit(_decode_step)/\
while/body/moe/experts/jit(_gmm_scaled)/pallas_call"}
""".replace("\\\n", "")


def test_kernels_named_from_the_compiled_program():
    from bench.profile import kernel_names
    names = kernel_names(MESH_HLO)
    assert names == {"shard_map.442": "_paged_attn_decode",
                     "shard_map.443": "_gmm_swiglu",
                     "_gmm_scaled.15": "_gmm_scaled"}
    tr = Trace.from_dict({
        "ops": [("while.1", 100, 1000), ("shard_map.443", 150, 200),
                ("all-gather.92", 400, 300), ("shard_map.442", 700, 100)],
        "modules": [("jit__decode_step(1)", 100, 1000)],
        "host": [("bench.window", 0, 2000)]})
    assert tr.op_seconds(r"gmm", "_decode_step") == 0
    tr.name_kernels(names)
    assert sorted(n for n, _, _ in tr.ops) == [
        "_gmm_swiglu.443", "_paged_attn_decode.442", "all-gather.92",
        "while.1"]
    assert tr.op_seconds(r"gmm", "_decode_step") == pytest.approx(200e-9)
    assert tr.op_seconds(r"paged_attn_decode", "_decode_step") == \
        pytest.approx(100e-9)

