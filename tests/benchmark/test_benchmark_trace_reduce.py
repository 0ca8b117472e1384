"""The reduction from trace events to device numbers: on events made by
hand, on the small trace recorded on the v5e, and the loader on a trace
this process records."""

import glob
import json
import os

import pytest

from benchmark_tiny import REPO  # noqa: F401  (puts the repo on the path)
from benchmarks import trace_reduce as tr

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "trace_v5e_small.json")


def dev(name, start, dur, chip=0, **stats):
    return dict({"chip": chip, "name": name, "start_ns": float(start),
                 "dur_ns": float(dur)}, **stats)


def host(name, start, dur):
    return {"name": tr.SPAN_PREFIX + name, "start_ns": float(start),
            "dur_ns": float(dur)}


def test_union_clip_total_and_gaps():
    busy = tr.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)])
    assert busy == [(0, 3), (5, 8)]
    assert tr.total(busy) == 6
    assert tr.clip(busy, (2, 6)) == [(2, 3), (5, 6)]
    assert tr.gaps(busy, (0, 10)) == [(3, 5), (8, 10)]
    assert tr.gaps([], (0, 4)) == [(0, 4)]
    assert tr.gaps([(0, 4)], (0, 4)) == []


def test_self_time_counts_nested_operations_once():
    events = [dev("while.1", 0, 100), dev("fusion.1", 10, 30),
              dev("fusion.2", 50, 40), dev("copy.3", 100, 5)]
    assert tr.self_times(events) == [30.0, 30.0, 40.0, 5.0]


def test_module_path_group_key_and_attention_core():
    op = "jit(_step)/jit(main)/transpose(jvp(BertForMaskedLM))/encoder/" \
         "layer_7/attention/query/dot_general"
    ev = dev("fusion.12", 0, 1, op_name=op)
    assert tr.module_path(ev) == "encoder/layer_7/attention/query"
    assert tr.group_key(ev) == "encoder/layer_*/attention/query [other]"
    assert not tr.is_attention_core(ev)
    core = dev("fusion.13", 0, 1,
               op_name="jit(_step)/encoder/layer_7/attention/exp")
    assert tr.is_attention_core(core)
    dropout = dev("fusion.14", 0, 1, op_name="jit(_step)/encoder/layer_7/"
                  "attention/Dropout_0/mul")
    assert tr.is_attention_core(dropout)
    assert not tr.is_attention_core(
        dev("fusion.15", 0, 1, op_name="jit(_step)/encoder/layer_7/output/add"))
    bare = dev("all-reduce.3", 0, 1)
    assert tr.module_path(bare) is None
    assert tr.group_key(bare) == "all-reduce"
    assert tr.group_key(dict(bare, module="jit_psum")) == "jit_psum/all-reduce"
    assert tr.is_mxu(dev("f", 0, 1, category="mxu fusion"))
    assert not tr.is_mxu(dev("f", 0, 1, category="loop fusion"))


def test_gap_goes_to_the_span_that_covers_most_of_it():
    spans = [host("optimizer", 0, 100), host("exchange", 100, 50),
             host("input", 40, 10)]
    assert tr.attribute((10, 30), spans) == "bench/optimizer"
    assert tr.attribute((90, 140), spans) == "bench/exchange"
    assert tr.attribute((42, 48), spans) == "bench/input"   # the innermost
    assert tr.attribute((500, 600), spans) == "host/none"


def test_reduce_on_hand_made_events():
    events = {
        "device": [
            dev("fusion.1", 100, 300, category="mxu fusion",
                op_name="jit(s)/enc/layer_0/attention/query/dot_general"),
            dev("fusion.2", 400, 100, category="loop fusion",
                op_name="jit(s)/enc/layer_0/attention/exp"),
            dev("fusion.3", 700, 200, category="mxu fusion",
                op_name="jit(s)/enc/layer_1/attention/query/dot_general"),
            dev("fusion.9", 5000, 100, category="loop fusion"),  # outside
        ],
        "host": [host("window", 0, 1000), host("grad", 0, 450),
                 host("optimizer", 450, 550)],
    }
    r = tr.reduce(events)
    assert r["window_source"] == "host_span" and r["chips"] == 1
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(600e-9)
    assert r["idle_share"] == pytest.approx(0.4)
    assert r["mxu_share"] == pytest.approx(500 / 600)
    events["device"].append(dev("all-reduce.1", 900, 50,
                                category="collective"))
    assert tr.reduce(events)["collective_s"] == pytest.approx(50e-9)
    events["device"].pop()
    assert r["attention_share"] == pytest.approx(100 / 600)
    assert r["device_ops"][0] == ["enc/layer_*/attention/query [mxu fusion]",
                                  pytest.approx(500e-9)]
    assert dict(map(tuple, r["idle_gaps"])) == {
        "bench/grad": pytest.approx(100e-9),
        "bench/optimizer": pytest.approx(300e-9)}
    assert r["longest_gaps"][0] == ["bench/optimizer", pytest.approx(200e-9)]
    assert r["events"] == 3


def test_reduce_averages_busy_time_over_chips_and_needs_device_events():
    events = {"device": [dev("a", 0, 100, chip=0), dev("b", 0, 50, chip=1)],
              "host": []}
    r = tr.reduce(events)
    assert r["window_source"] == "device_extent" and r["chips"] == 2
    assert r["busy_s"] == pytest.approx(75e-9)
    assert r["device_ops"] == [["a", pytest.approx(50e-9)],
                               ["b", pytest.approx(25e-9)]]   # a chip's mean
    assert r["collective_s"] == 0.0
    assert r["idle_share"] == 0.0          # rank 0's chip is busy throughout
    assert r["attention_share"] is None    # no module path in this trace
    assert tr.reduce({"device": [], "host": [host("window", 0, 10)]}) is None


def test_reduce_on_the_recorded_v5e_trace():
    """400 device operations cut from a traced step on the chip.  The
    expected numbers were worked out by another, slower method (a
    boolean timeline at 1 ns) when the trace was cut."""
    with open(RECORDED) as f:
        recorded = json.load(f)
    events, want = recorded["events"], recorded["expected"]
    assert len(events["device"]) == want["events"] == 400
    r = tr.reduce(events)
    assert r["window_source"] == "device_extent"   # the cut has no window
    assert r["events"] == want["events"]
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert r["idle_share"] == pytest.approx(want["idle_share"], rel=1e-6)
    assert r["mxu_share"] == pytest.approx(want["mxu_share"], rel=1e-9)
    assert [k for k, _ in r["device_ops"][:3]] == want["top_ops"]
    assert 0.85 < r["mxu_share"] < 0.95 and r["idle_share"] < 0.01
    assert r["attention_share"] is None   # the trace carries no path
    # The only gaps of note lie under the host's dispatch of the step.
    assert r["idle_gaps"][0][0] == "bench/step"


def test_parse_instruction_on_the_v5e_names():
    fusion = ("%convolution_add_fusion.23 = bf16[64,128,4096]{2,1,0:T(8,128)"
              "(2,1)} fusion(bf16[4096]{0:T(1024)(128)(2,1)S(1)} %copy-done.1"
              "), kind=kOutput, calls=%fused_computation.444")
    assert tr.parse_instruction(fusion) == ("convolution_add_fusion.23",
                                            "mxu fusion")
    loop = ("%shift-right-logical_add_fusion.2 = u32[128]{0:T(128)} fusion(),"
            " kind=kLoop, calls=%fused_computation.7624")
    assert tr.parse_instruction(loop)[1] == "loop fusion"
    copy = ("%copy-start.24 = (bf16[30522,1024]{1,0:T(8,128)(2,1)}, u32[]"
            "{:S(2)}) copy-start(bf16[30522,1024]{1,0:T(8,128)(2,1)S(1)} "
            "%convert_element_type.991)")
    assert tr.parse_instruction(copy) == ("copy-start.24", "copy-start")
    assert tr.parse_instruction("%all-reduce.7 = f32[8]{0} all-reduce(f32[8]"
                                "{0} %p), replica_groups={}")[1] == \
        "collective"
    assert tr.parse_instruction("something else") == ("something", "other")
    text = ('  ROOT %fusion.3 = f32[2]{0} fusion(f32[2]{0} %p), kind=kLoop, '
            'calls=%fc, metadata={op_name="jit(f)/enc/layer_0/attention/exp"'
            ' source_file="x.py"}\n  %a.1 = f32[] add(f32[] %x, f32[] %y)')
    assert tr.op_names(text) == {"fusion.3":
                                 "jit(f)/enc/layer_0/attention/exp"}


def test_loader_finds_the_benchmark_spans_in_a_trace_made_here(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((64, 64))
    float(f(x))
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation(tr.SPAN_PREFIX + "step"):
            y = f(x)
        with jax.profiler.TraceAnnotation(tr.SPAN_PREFIX + "fetch"):
            float(y)
        with jax.profiler.TraceAnnotation("not-ours"):
            pass
    jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    events = tr.load_events(path)
    names = [s["name"] for s in events["host"]]
    assert sorted(names) == ["bench/fetch", "bench/step", "bench/window"]
    window = next(s for s in events["host"] if s["name"] == tr.WINDOW_SPAN)
    for s in events["host"]:
        assert s["start_ns"] >= window["start_ns"] and s["dur_ns"] > 0
        assert s["start_ns"] + s["dur_ns"] <= \
            window["start_ns"] + window["dur_ns"]
    assert events["device"] == []          # a CPU has no device plane
    assert tr.reduce(events) is None
    assert any(line.startswith("plane /host:CPU")
               for line in tr.describe(path))
