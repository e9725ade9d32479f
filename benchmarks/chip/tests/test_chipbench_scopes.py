"""Layer attribution and HBM bytes (``scopes.py``) on two small real traces
recorded on a TPU v5e by ``run.run_cell`` with ``--trace 1`` and a window
of a few queries (``data/*.xplane.pb.gz``): four offline batches of 128
images through ``forward_fused_groups``, and two single-image queries
through ``forward``, both at the default matmul precision.  Beside each is
the optimized HLO text of the scoped program compiled for a described v5e
at the same precision and batch (``data/*.hlo.txt.gz``): its ENTRY
instructions are the trace's, name for name and shape for shape (the
scopes change metadata only).  The readers are given that map in place of
the compile they make on the chip."""

import gzip
import types

import jax
import pytest
from chipbench_testlib import BENCH, small_bench

import devtrace
import scopes
import spec

DATA = BENCH / "tests" / "data"
CELLS = {"offline": "resnet18-fused4.offline",
         "singlestream": "resnet18-layerwise.singlestream"}
# the matmul precision the recorded program ran at
PRECISION = "default"
READERS = {"offline": ["group2_roofline.offline", "group3_roofline.offline",
                       "tail_roofline.offline", "hbm_mb_per_image.offline"],
           "singlestream": ["group1_roofline.singlestream",
                            "hbm_mb_per_image.singlestream"]}
BENCHMARK = spec.Bench()
V5E = "TPU v5 lite"


def recorded(traffic: str):
    raw = gzip.decompress((DATA / f"{traffic}_tpu_v5e.xplane.pb.gz").read_bytes())
    trace = devtrace.reduce_profile(
        jax.profiler.ProfileData.from_serialized_xspace(raw))
    text = gzip.decompress(
        (DATA / f"{traffic}_tpu_v5e.hlo.txt.gz").read_bytes()).decode()
    return trace, text


def fake_run(traffic: str, trace) -> types.SimpleNamespace:
    """What ``run.run_cell`` gives a reader, for the recorded window."""
    w = BENCHMARK.workload(CELLS[traffic])
    cfg = dict(BENCHMARK.config(w["config"]), matmul_precision=PRECISION)
    t = BENCHMARK.traffic(traffic)
    queries = sum(1 for name, _, _ in trace.spans if name == "query.call")
    return types.SimpleNamespace(
        cfg=cfg, adapter=BENCHMARK.adapter(cfg["model"]), traffic=t,
        peaks=BENCHMARK.peaks(V5E), flops_peak=BENCHMARK.flops_peak(V5E, cfg),
        trace=trace,
        traced=types.SimpleNamespace(attempted=queries,
                                     images=queries * t["batch"]))


@pytest.fixture(scope="module", params=list(CELLS))
def cell(request):
    trace, text = recorded(request.param)
    run = fake_run(request.param, trace)
    return request.param, run, scopes.entry_map(text, scopes.layer_names(run.cfg))


@pytest.fixture
def given_map(monkeypatch):
    """Readers use ``ops`` in place of the compile they make on the chip."""
    def use(ops):
        monkeypatch.setattr(scopes, "op_map", lambda _run: ops)
    return use


def test_every_op_of_the_window_is_in_the_map_with_its_dims(cell):
    _, run, ops = cell
    evs = [t for device in run.trace.ops.values() for t, _, _ in device]
    assert evs and run.traced.attempted >= 2
    for text in evs:
        name, result, _, _ = scopes.split_instruction(text)
        assert ops[name][0] == scopes.out_dims(result), text[:120]
    # every computing op is in a layer; the unscoped ones move weights or
    # the input
    for text in evs:
        name, _, opcode, _ = scopes.split_instruction(text)
        if ops[name][1] is None:
            assert opcode in {"copy-start", "copy-done", "async-start",
                              "async-done", "custom-call", "copy", "pad"}, text


def test_group_and_unscoped_seconds_add_up_to_op_seconds(cell, given_map):
    _, run, ops = cell
    given_map(ops)
    seconds = scopes.group_seconds(run)
    assert set(seconds) == {*run.cfg["groups"], scopes.UNSCOPED}
    assert sum(seconds.values()) == pytest.approx(run.trace.op_seconds(),
                                                  rel=1e-12)
    assert all(seconds[g] > 0 for g in run.cfg["groups"])


def test_offline_group1_by_scope_agrees_with_the_shape_rule(given_map):
    trace, text = recorded("offline")
    run = fake_run("offline", trace)
    given_map(scopes.entry_map(text, scopes.layer_names(run.cfg)))
    by_scope = scopes.group_seconds(run)["group1"]
    by_shape = trace.op_seconds(
        run.adapter.group_work(run.cfg, "group1", 128)["rows"])
    assert by_scope == pytest.approx(by_shape, rel=0.02)


def test_every_reader_reads_the_recorded_window(cell, given_map):
    traffic, run, ops = cell
    given_map(ops)
    for name in READERS[traffic]:
        value = BENCHMARK.metric_reader(name).read(run)
        assert value is not None and value > 0, name
        if "roofline" in name:
            assert value < 100, (name, value)


def test_a_map_lacking_one_op_makes_every_reader_return_none(cell, given_map):
    traffic, run, ops = cell
    name = next(iter(scopes.split_instruction(t)[0]
                     for t, _, _ in next(iter(run.trace.ops.values()))))
    given_map({k: v for k, v in ops.items() if k != name})
    for reader in READERS[traffic]:
        assert BENCHMARK.metric_reader(reader).read(run) is None, reader
    # nor may an op keep its name with other dimensions
    dims, layer = ops[name]
    given_map(dict(ops, **{name: (dims + (1,), layer)}))
    for reader in READERS[traffic]:
        assert BENCHMARK.metric_reader(reader).read(run) is None, reader


def test_no_device_ops_or_unreadable_ops_read_none(given_map):
    trace, text = recorded("singlestream")
    run = fake_run("singlestream", trace)
    given_map(scopes.entry_map(text, scopes.layer_names(run.cfg)))
    run.trace = types.SimpleNamespace(ops={})
    for reader in READERS["singlestream"]:
        assert BENCHMARK.metric_reader(reader).read(run) is None
    # an event that is no HLO instruction is not guessed at either
    run.trace = types.SimpleNamespace(ops={"/device:TPU:0": [
        ("%copy-start.9 = f32[64]{0} copy-start(f32[64]{0} %p)", 0, 1)]})
    given_map({"copy-start.9": ((64,), None)})
    for reader in READERS["singlestream"]:
        assert BENCHMARK.metric_reader(reader).read(run) is None
    run.trace.ops["/device:TPU:0"] = [("XLA program", 0, 1)]
    for reader in READERS["singlestream"]:
        assert BENCHMARK.metric_reader(reader).read(run) is None


# --- HBM bytes, by hand ------------------------------------------------------

F32 = 4


def test_hbm_bytes_of_a_fusion_counts_its_hbm_operands_and_result():
    # the stem conv at batch 128: image and weights in, map out, all in HBM
    text = ("%fusion.11 = f32[128,112,112,64]{0,3,2,1:T(8,128)} fusion("
            "f32[128,224,224,3]{0,2,3,1:T(8,128)} %x.1, "
            "f32[7,7,3,64]{3,1,2,0:T(8,128)} %p__conv1__.1), kind=kOutput, "
            "calls=%fused_computation.11")
    assert scopes.hbm_bytes(text) == F32 * (128 * 224 * 224 * 3
                                            + 7 * 7 * 3 * 64
                                            + 128 * 112 * 112 * 64)
    # an operand or result in VMEM (S(1)) is not HBM traffic, and an
    # operand named twice is read once
    text = ("%fusion = f32[128,56,56,64]{0,3,2,1:T(8,128)S(1)} fusion("
            "f32[128,112,112,64]{0,3,2,1:T(8,128)} %fusion.11, "
            "f32[64]{0:T(128)S(1)} %copy-done.41, "
            "f32[128,112,112,64]{0,3,2,1:T(8,128)} %fusion.11), kind=kOutput")
    assert scopes.hbm_bytes(text) == F32 * 128 * 112 * 112 * 64


def test_hbm_bytes_of_a_copy_start_done_pair_counts_the_copy_once():
    start = ("%copy-start.2 = (f32[3,3,64,64]{3,2,1,0:T(8,128)S(1)}, "
             "f32[3,3,64,64]{3,2,1,0:T(8,128)}, u32[]{:S(2)}) copy-start("
             "f32[3,3,64,64]{3,2,1,0:T(8,128)} %p__s1b1____conv2__.1)")
    done = ("%copy-done.2 = f32[3,3,64,64]{3,2,1,0:T(8,128)S(1)} copy-done(("
            "f32[3,3,64,64]{3,2,1,0:T(8,128)S(1)}, "
            "f32[3,3,64,64]{3,2,1,0:T(8,128)}, u32[]{:S(2)}) %copy-start.2)")
    assert scopes.hbm_bytes(start) == F32 * 3 * 3 * 64 * 64
    assert scopes.hbm_bytes(done) == 0
    # a copy from HBM to HBM reads and writes
    hbm_to_hbm = start.replace("T(8,128)S(1)}", "T(8,128)}")
    assert scopes.hbm_bytes(hbm_to_hbm) == 2 * F32 * 3 * 3 * 64 * 64


def test_hbm_bytes_of_a_slice_start_counts_the_slice_not_the_source():
    start = ("%slice-start.22 = ((f32[3,3,128,256]{3,2,1,0:T(8,128)}), "
             "f32[1,3,128,256]{3,2,1,0:T(8,128)S(1)}, s32[]{:S(2)}) "
             "async-start(f32[3,3,128,256]{3,2,1,0:T(8,128)} "
             "%p__s3b1____conv1__.1), calls=%async_computation.22")
    done = ("%slice-done.22 = f32[1,3,128,256]{3,2,1,0:T(8,128)S(1)} "
            "async-done(((f32[3,3,128,256]{3,2,1,0:T(8,128)}), "
            "f32[1,3,128,256]{3,2,1,0:T(8,128)S(1)}, s32[]{:S(2)}) "
            "%slice-start.22)")
    assert scopes.hbm_bytes(start) == F32 * 1 * 3 * 128 * 256
    assert scopes.hbm_bytes(done) == 0


def test_hbm_bytes_are_logical_bytes_by_element_type():
    text = ("%fusion.5 = (bf16[56,1,7,1,64]{4,2,3,0,1:T(8,128)(2,1)}, "
            "pred[8,16]{0,1:T(8,128)(4,1)}) fusion(s8[10]{0} %a, "
            "f32[]{:T(128)} %b)")
    assert scopes.hbm_bytes(text) == 2 * 56 * 7 * 64 + 8 * 16 + 10 + 4


def test_recorded_hand_counted_instructions_are_in_the_traces():
    # the texts above are the recorded program's own instructions
    texts = {t.split(" = ")[1] for traffic in CELLS
             for device in recorded(traffic)[0].ops.values()
             for t, _, _ in device}
    assert any(t.startswith("f32[128,112,112,64]{0,3,2,1:T(8,128)} fusion("
                            "f32[128,224,224,3]") for t in texts)
    assert any(" copy-start(" in t for t in texts)
    assert any(" async-start(" in t and "S(1)" in t for t in texts)


def test_singlestream_moves_at_least_the_weights(given_map):
    trace, text = recorded("singlestream")
    run = fake_run("singlestream", trace)
    given_map(scopes.entry_map(text, scopes.layer_names(run.cfg)))
    params = jax.eval_shape(lambda k: run.adapter.init_params(run.cfg, k),
                            jax.random.key(0))
    weights = sum(p.size * p.dtype.itemsize for p in jax.tree.leaves(params))
    per_query = scopes.window_hbm_bytes(run) / run.traced.attempted
    assert per_query >= weights
    assert BENCHMARK.metric_reader("hbm_mb_per_image.singlestream").read(run) \
        == pytest.approx(per_query / 1e6)


# --- instruction text --------------------------------------------------------

def test_split_instruction_reads_hlo_text_and_trace_names():
    hlo = ('  ROOT %broadcast_add_fusion = f32[2,1000]{1,0} fusion('
           '%dot_general.1, %p__fc_b__.1), kind=kLoop, calls=%fc, '
           'metadata={op_name="jit(call)/head/add" source_file="r.py"}')
    name, result, opcode, args = scopes.split_instruction(hlo)
    assert (name, result, opcode) == ("broadcast_add_fusion",
                                      "f32[2,1000]{1,0}", "fusion")
    assert args == "(%dot_general.1, %p__fc_b__.1)"
    assert scopes.entry_map("HloModule m\n\nENTRY %main (x: f32[2]) -> f32[2] {\n"
                            + hlo + "\n}\n", ["head"]) \
        == {"broadcast_add_fusion": ((2, 1000), "head")}
    trace = ("%slice-start = ((f32[512,1000]{0,1:T(8,128)}), "
             "f32[512,256]{0,1:T(8,128)S(1)}, s32[]{:S(2)}) async-start("
             "f32[512,1000]{0,1:T(8,128)} %p__fc_w__.1), calls=%ac")
    name, result, opcode, _ = scopes.split_instruction(trace)
    assert (name, opcode) == ("slice-start", "async-start")
    assert scopes.out_dims(result) == (512, 1000)
    with pytest.raises(ValueError):
        scopes.split_instruction("not an instruction")


def test_op_map_compiles_the_cells_program_on_the_cpu(tmp_path):
    bench = small_bench(tmp_path)
    for traffic, cell in CELLS.items():
        cfg = bench.config(bench.workload(cell)["config"])
        run = types.SimpleNamespace(cfg=cfg, adapter=bench.adapter(cfg["model"]),
                                    traffic=bench.traffic(traffic))
        ops = scopes.op_map(run)
        layers = {layer for _, layer in ops.values()}
        assert layers - {None} == set(scopes.layer_names(cfg)), traffic
        assert scopes.op_map(run) is ops
