"""The one span API (common/timeline.py) and its three readers: the
profiler's host plane, the metrics registry and the Timeline file, on
one clock; the cold spans of start-up and of every compilation."""

import collections
import glob
import importlib.util
import json
import os
import subprocess
import sys
import textwrap
import threading
import time

import pytest

from multiproc import REPO

from horovod_tpu.common import metrics
from horovod_tpu.common import timeline as tl

_SPEC = importlib.util.spec_from_file_location(
    "validate_trace", os.path.join(REPO, "tools", "validate_trace.py"))
validate_trace = importlib.util.module_from_spec(_SPEC)
_SPEC.loader.exec_module(validate_trace)


def _registry(name):
    """(count, seconds) of ``hvd/<name>`` in ``hvd_span_seconds``."""
    spans = metrics.snapshot()["histograms"].get("hvd_span_seconds", {})
    h = spans.get("span=hvd/" + name)
    return (h["count"], h["sum"]) if h else (0, 0.0)


def test_span_lies_in_the_profilers_host_plane(tmp_path):
    """Under a profiler session a span is ``hvd/<name>`` in the host
    plane, inside the annotation that encloses it, with its arguments,
    as long as the registry says, and at the wall-clock instant the
    span's own clock gives it."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    count0, seconds0 = _registry("test_traced")
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("enclosing"):
            with tl.span("test_traced", tensor="grad/w", tensors=3) as sp:
                time.sleep(0.03)
    finally:
        jax.profiler.stop_trace()
    count, seconds = _registry("test_traced")
    assert count == count0 + 1
    assert seconds - seconds0 == pytest.approx(sp.seconds, rel=1e-6)

    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    profile = jax.profiler.ProfileData.from_file(path)
    found, started = {}, None
    for plane in profile.planes:
        stats = dict(plane.stats)
        if "profile_start_time" in stats:
            started = stats["profile_start_time"]
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in ("enclosing", "hvd/test_traced"):
                    found[ev.name] = (line.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns,
                                      dict(ev.stats))
    assert set(found) == {"enclosing", "hvd/test_traced"}
    outer, inner = found["enclosing"], found["hvd/test_traced"]
    assert inner[0] == outer[0]                       # one thread's line
    assert outer[1] <= inner[1] and inner[2] <= outer[2]   # nested
    assert inner[3] == {"tensor": "grad/w", "tensors": 3}
    assert (inner[2] - inner[1]) / 1e9 == pytest.approx(sp.seconds,
                                                        rel=0.10)
    # The host plane's clock is the wall clock, and so is the span's.
    assert started is not None
    assert (started + inner[1]) / 1e9 == pytest.approx(tl.wall(sp.t0),
                                                       abs=2e-3)


def test_a_hot_span_keeps_count_and_seconds_and_no_record():
    """With no session and no Timeline a span leaves nothing behind
    but its count and seconds."""
    assert tl._sink is None
    kept = len(tl.spans())
    count0, seconds0 = _registry("test_hot")
    for _ in range(100):
        with tl.span("test_hot", tensor="x"):
            pass
    count, seconds = _registry("test_hot")
    assert count == count0 + 100 and seconds > seconds0
    assert len(tl.spans()) == kept


def test_a_discarded_span_is_left_out_but_still_timed():
    count0, _ = _registry("test_discarded")
    with tl.span("test_discarded") as sp:
        time.sleep(0.002)
        sp.discard()
    assert _registry("test_discarded")[0] == count0
    assert sp.seconds >= 0.002


def test_cold_spans_know_their_parent_thread_and_arguments():
    def other_thread():
        with tl.span("test_cold_elsewhere", cold=True):
            pass

    with tl.span("test_cold_parent", cold=True):
        with tl.span("test_cold_child", cold=True, program="f"):
            thread = threading.Thread(target=other_thread, name="elsewhere")
            thread.start()
            thread.join(10)
        tl.record("test_cold_reported", time.time() - 0.5, time.time(),
                  program="g")
    by_name = {s["name"]: s for s in tl.spans()}
    child = by_name["hvd/test_cold_child"]
    parent = by_name["hvd/test_cold_parent"]
    assert child["parent"] == "hvd/test_cold_parent"
    assert child["args"] == {"program": "f"}
    assert parent["parent"] is None
    assert parent["start"] <= child["start"] <= child["end"] <= parent["end"]
    assert abs(parent["end"] - time.time()) < 1.0      # the wall clock
    elsewhere = by_name["hvd/test_cold_elsewhere"]
    assert elsewhere["parent"] is None and elsewhere["thread"] == "elsewhere"
    reported = by_name["hvd/test_cold_reported"]
    assert reported["parent"] == "hvd/test_cold_parent"
    assert reported["end"] - reported["start"] == pytest.approx(0.5, abs=0.01)


def test_the_cold_record_is_bounded_by_name(monkeypatch):
    """Many spans of one name push out that name's oldest, and nothing
    of another name: a run that traces thousands of small programs
    keeps its start-up."""
    assert tl.COLD_SPANS_KEPT >= 1024
    monkeypatch.setattr(tl, "COLD_SPANS_KEPT", 8)
    monkeypatch.setattr(tl, "_cold", collections.defaultdict(
        lambda: collections.deque(maxlen=tl.COLD_SPANS_KEPT)))
    tl.record("test_startup", 0.0, 1.0)
    for i in range(20):
        tl.record("test_bounded", 2.0 + i, 3.0 + i, i=i)
    assert [s["args"].get("i") for s in tl.spans()] == \
        [None] + list(range(12, 20))


def test_timeline_file_and_cold_list_agree_on_one_clock(tmp_path):
    """The Timeline's ``ts`` and the cold span's start are one reading:
    they agree to well under a millisecond; the per-tensor activity and
    the cycle mark come from the spans' own timings."""
    path = tmp_path / "timeline.json"
    timeline = tl.Timeline(str(path), rank=0, mark_cycles=True)
    tl.set_sink(timeline)
    try:
        with tl.span("test_on_file", cold=True, program="f"):
            time.sleep(0.002)
        timeline.negotiate_start("grad/w", "ALLREDUCE")
        timeline.negotiate_end("grad/w")
        with tl.span("cycle"):
            with tl.span("dispatch", op="ALLREDUCE", tensor="grad/w",
                         tensors=2, bytes=64) as dispatch:
                time.sleep(0.002)
    finally:
        tl.set_sink(None)
        timeline.close()
    events = json.loads(path.read_text())
    assert validate_trace.validate_events(events) == []
    cold = [s for s in tl.spans() if s["name"] == "hvd/test_on_file"][-1]
    on_file, = [e for e in events if e.get("name") == "hvd/test_on_file"]
    assert on_file["ph"] == "X" and on_file["args"] == {"program": "f"}
    assert abs(on_file["ts"] - cold["start"] * 1e6) < 1000.0
    assert abs(on_file["dur"] - (cold["end"] - cold["start"]) * 1e6) < 1000.0
    assert abs(on_file["ts"] / 1e6 - time.time()) < 60.0   # the wall clock

    span, = [e for e in events if e.get("name") == "hvd/dispatch"]
    begin, = [e for e in events if e.get("name") == "XLA_ALLREDUCE"]
    assert begin["ts"] == span["ts"] and begin["pid"] == 0
    end = [e for e in events if e["ph"] == "E" and e["tid"] == begin["tid"]]
    assert end[-1]["ts"] == span["ts"] + span["dur"]
    assert span["dur"] == pytest.approx(dispatch.seconds * 1e6, rel=1e-9)
    cycle, = [e for e in events if e.get("name") == "hvd/cycle"]
    mark, = [e for e in events if e.get("name") == "CYCLE_START"]
    assert mark["ts"] == cycle["ts"]
    lanes = {e["args"]["name"]: e["pid"] for e in events if e["ph"] == "M"}
    assert lanes["grad/w"] == 0
    assert lanes[threading.current_thread().name] == 1


def test_init_children_add_up_and_the_eager_path_is_clocked_once():
    import numpy as np

    import horovod_tpu as hvd
    hist0 = metrics.snapshot()["histograms"]
    hvd.init()
    try:
        hvd.allreduce(np.ones(4, np.float32), name="test_spans.x")
    finally:
        hvd.shutdown()
    spans = hvd.spans()
    init = [s for s in spans if s["name"] == "hvd/init"][-1]
    children = [s for s in spans if s["parent"] == "hvd/init"
                and s["start"] >= init["start"]]
    # A world of one has no init/distributed; the other four are there.
    assert [c["name"] for c in children] == [
        "hvd/init/rendezvous", "hvd/init/device_client", "hvd/init/backend",
        "hvd/init/runtime"]
    total = sum(c["end"] - c["start"] for c in children)
    whole = init["end"] - init["start"]
    # Within 5 %; where a warm init takes under a millisecond, within
    # the `with` statements' own half millisecond.
    assert 0 <= whole - total <= max(0.05 * whole, 5e-4)
    down = [s for s in spans if s["name"] == "hvd/shutdown"][-1]
    assert [s["name"] for s in spans if s["parent"] == "hvd/shutdown"
            and s["start"] >= down["start"]] == [
        "hvd/shutdown/runtime", "hvd/shutdown/backend",
        "hvd/shutdown/detach"]
    assert any(s["name"] == "hvd/import" for s in spans)

    # One timing of the cycle feeds the span and hvd_cycle_seconds.
    hist = metrics.snapshot()["histograms"]

    def grew(name, key=None):
        def total(h):
            h = h.get(name, {})
            h = h.get(key, {}) if key else h
            return h.get("count", 0), h.get("sum", 0.0)
        (c1, s1), (c0, s0) = total(hist), total(hist0)
        return c1 - c0, s1 - s0
    cycles, cycle_s = grew("hvd_cycle_seconds")
    span_cycles, span_s = grew("hvd_span_seconds", "span=hvd/cycle")
    assert cycles == span_cycles >= 1
    assert cycle_s == pytest.approx(span_s, rel=1e-9)
    for name in ("submit", "wait", "negotiate", "fuse", "dispatch"):
        assert grew("hvd_span_seconds", "span=hvd/" + name)[0] >= 1, name
    assert grew("hvd_submit_latency_seconds")[0] == 1


_COMPILES = textwrap.dedent("""
    import json
    import jax, jax.numpy as jnp
    import horovod_tpu as hvd
    from horovod_tpu.common import compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compile_cache.enable()
    compile_cache.enable()      # subscribing twice would count twice

    @jax.jit
    def inner_function_of_this_test(x):
        return jnp.tanh(x @ x)

    @jax.jit
    def a_function_of_this_test(x):
        return inner_function_of_this_test(x).sum()

    a_function_of_this_test(jnp.ones((32, 32))).block_until_ready()
    mine = [[s["name"], s["args"]["program"], s["end"] - s["start"]]
            for s in hvd.spans() if s["name"].startswith("hvd/compile/")
            and "a_function_of_this_test" in s["args"]["program"]]
    inner = [s["name"] for s in hvd.spans()
             if "inner_function" in str(s["args"].get("program"))]
    print(json.dumps({"spans": mine, "inner": inner,
                      "requests": hvd.metrics_snapshot()[
                          "counters"]["hvd_compile_requests_total"]}))
""")


@pytest.fixture(scope="module")
def two_processes(tmp_path_factory):
    """The same jitted function in two processes that share a cache
    directory: the first compiles it, the second loads it."""
    cache = tmp_path_factory.mktemp("jax_cache")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               JAX_COMPILATION_CACHE_DIR=str(cache))
    out = []
    for _ in range(2):
        done = subprocess.run([sys.executable, "-c", _COMPILES], env=env,
                              capture_output=True, text=True, timeout=120)
        assert done.returncode == 0, done.stderr[-3000:]
        out.append(json.loads(done.stdout.strip().splitlines()[-1]))
    return out


def test_a_jit_yields_trace_lower_and_backend_compile_spans(two_processes):
    first = two_processes[0]
    phases = [name for name, _, _ in first["spans"]]
    assert phases == ["hvd/compile/trace", "hvd/compile/lower",
                      "hvd/compile/backend_compile"]
    assert all(seconds > 0 for _, _, seconds in first["spans"])
    # Traced inside the outer function's trace: part of that span.
    assert first["inner"] == []
    assert set(first["requests"]) == {"outcome=miss"}
    assert first["requests"]["outcome=miss"] >= 1


def test_a_second_process_yields_a_cache_load_and_a_hit(two_processes):
    first, second = two_processes
    phases = [name for name, _, _ in second["spans"]]
    assert phases == ["hvd/compile/trace", "hvd/compile/lower",
                      "hvd/compile/cache_load"]
    assert set(second["requests"]) == {"outcome=hit"}
    assert second["requests"]["outcome=hit"] == \
        first["requests"]["outcome=miss"]


def test_a_compile_the_cache_does_not_keep_is_uncached():
    """In this process the persistent cache is off: a compilation is
    neither hit nor miss."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.common import compile_cache
    compile_cache._subscribe()
    before = metrics.snapshot()["counters"].get(
        "hvd_compile_requests_total", {}).get("outcome=uncached", 0)

    @jax.jit
    def another_function_of_this_test(x):
        return (x * 3).sum()

    another_function_of_this_test(jnp.ones(7)).block_until_ready()
    after = metrics.snapshot()["counters"]["hvd_compile_requests_total"][
        "outcome=uncached"]
    assert after >= before + 1
    last = [s for s in tl.spans() if "another_function_of_this_test"
            in str(s["args"].get("program"))]
    assert [s["name"] for s in last] == [
        "hvd/compile/trace", "hvd/compile/lower",
        "hvd/compile/backend_compile"]


def test_jitted_steps_name_their_optimizer_and_loss():
    """The device trace finds AdamW and the loss under a path of their
    own: the scopes are in the lowered step's locations."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models.gpt import GPTConfig
    from horovod_tpu.parallel import build_mesh
    from horovod_tpu.training import make_gpt_train_step
    mesh = build_mesh({"dp": 1}, jax.devices()[:1])
    config = GPTConfig(vocab_size=64, hidden_size=16, num_layers=1,
                       num_heads=2, intermediate_size=32,
                       max_position_embeddings=16)
    init_fn, step_fn, _ = make_gpt_train_step(config, mesh)
    ids = jnp.zeros((2, 16), jnp.int32)
    params, opt_state = init_fn(jax.random.PRNGKey(0), ids)
    text = step_fn.lower(params, opt_state, ids).as_text(debug_info=True)
    assert "jit(step_fn)/optimizer/" in text
    assert "jvp(loss)/" in text and "transpose(jvp(loss))/" in text


def _init_and_its_children():
    spans = tl.spans()
    init = [s for s in spans if s["name"] == "hvd/init"][-1]
    return init, [s for s in spans if s["parent"] == "hvd/init"
                  and s["start"] >= init["start"]]


def test_init_starts_the_device_client_in_a_world_of_one_and_again():
    """``hvd.init()`` returns with the devices held whatever the world's
    size, and says what it found; a second incarnation records the span
    again."""
    import jax

    import horovod_tpu as hvd
    seen = []
    for _ in range(2):
        hvd.init()
        hvd.shutdown()
        init, children = _init_and_its_children()
        client, = [c for c in children
                   if c["name"] == "hvd/init/device_client"]
        assert client["args"] == {"platform": jax.devices()[0].platform,
                                  "devices": len(jax.devices())}
        assert init["start"] <= client["start"] <= client["end"] \
            <= init["end"]
        order = [c["name"] for c in children]
        assert order.index("hvd/init/rendezvous") \
            < order.index("hvd/init/device_client") \
            < order.index("hvd/init/backend")
        seen.append(client["start"])
    assert seen[1] > seen[0]


def test_modules_the_package_does_not_import_record_their_own_import():
    import horovod_tpu.training  # noqa: F401
    imports = {s["args"].get("module"): s for s in tl.spans()
               if s["name"] == "hvd/import"}
    assert {"horovod_tpu", "horovod_tpu.models",
            "horovod_tpu.training"} <= set(imports)
    for span in imports.values():
        assert span["end"] > span["start"]
    # The package's own import ended before a late module's began.
    assert imports["horovod_tpu"]["end"] \
        <= imports["horovod_tpu.training"]["start"]


def _tiny_gpt():
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models.gpt import GPTConfig
    from horovod_tpu.parallel import build_mesh
    from horovod_tpu.training import make_gpt_train_step
    mesh = build_mesh({"dp": 1}, jax.devices()[:1])
    config = GPTConfig(vocab_size=64, hidden_size=16, num_layers=1,
                       num_heads=2, intermediate_size=32,
                       max_position_embeddings=16)
    init_fn, step_fn, _ = make_gpt_train_step(config, mesh)
    ids = jnp.arange(32, dtype=jnp.int32).reshape(2, 16) % 64

    def step(fn, state):
        *state, loss = fn(*state, ids)
        return tuple(state), loss
    return (lambda: init_fn(jax.random.PRNGKey(0), ids)), step_fn, step, \
        lambda state: (*state, ids)


def _tiny_bert():
    import jax

    from horovod_tpu.models.bert import bert_tiny_config
    from horovod_tpu.parallel import build_mesh
    from horovod_tpu.training import (make_bert_batch,
                                      make_bert_pretrain_step)
    mesh = build_mesh({"dp": 1}, jax.devices()[:1])
    config = bert_tiny_config()
    make_jitted, _ = make_bert_pretrain_step(config, mesh)
    batch = make_bert_batch(2, 16, config.vocab_size)
    init_fn, step_fn = make_jitted(batch)
    return (lambda: init_fn(jax.random.PRNGKey(0), batch)), step_fn, \
        (lambda fn, state: fn(state, batch)), lambda state: (state, batch)


@pytest.mark.parametrize("family", ["gpt", "bert"])
def test_a_programs_first_call_is_one_span_around_its_compile_spans(family):
    """Each program ``training.py`` hands out records its first call
    once, from before the call until its outputs are ready, with the
    request's compile spans inside; what it hands out is still the
    jitted function, and computes what the bare one computes."""
    import jax
    import numpy as np

    from horovod_tpu.common import compile_cache
    compile_cache._subscribe()
    since = tl.wall(time.perf_counter())
    init, step_fn, step, step_args = {"gpt": _tiny_gpt,
                                      "bert": _tiny_bert}[family]()
    assert isinstance(step_fn, compile_cache.first_call)
    state, bare_state = init(), init()
    losses, bare_losses = [], []
    for _ in range(4):
        state, loss = step(step_fn, state)
        bare_state, bare_loss = step(step_fn._jitted, bare_state)
        losses.append(np.asarray(loss))
        bare_losses.append(np.asarray(bare_loss))
    assert [v.tobytes() for v in losses] == \
        [v.tobytes() for v in bare_losses]
    for mine, bare in zip(jax.tree.leaves(state),
                          jax.tree.leaves(bare_state)):
        assert np.asarray(mine).tobytes() == np.asarray(bare).tobytes()

    # Still a jitted function to its callers.
    assert "optimizer" in step_fn.lower(*step_args(state)).as_text(
        debug_info=True)
    assert step_fn.trace(*step_args(state)).jaxpr.eqns

    mine = [s for s in tl.spans() if s["start"] >= since]
    calls = [s for s in mine if s["name"] == "hvd/program/first_call"]
    # init() ran twice: the causal-LM builder jits a new init program a
    # call, BERT's is one program.  The step: once, whoever called it.
    kinds = collections.Counter(s["args"]["kind"] for s in calls)
    assert kinds == {"init": 2 if family == "gpt" else 1, "step": 1}
    for kind in ("init", "step"):
        # The first of its kind compiled (JAX keeps the executable for
        # the causal-LM builder's second, equal, init program).
        call = [s for s in calls if s["args"]["kind"] == kind][0]
        program = call["args"]["program"]
        inside = [s for s in mine if s["parent"] == call["name"]
                  and call["start"] <= s["start"] and s["end"] <= call["end"]]
        assert all(s["name"].startswith("hvd/compile/") for s in inside)
        closing = [s for s in inside
                   if s["args"]["program"] == "jit(%s)" % program]
        # (Where another test turned the persistent cache on in this
        # process, the request may end in a load.)
        assert [s["name"] for s in closing] in (
            ["hvd/compile/lower", "hvd/compile/backend_compile"],
            ["hvd/compile/lower", "hvd/compile/cache_load"])
        assert call["end"] >= closing[-1]["end"]
        assert any(s["name"] == "hvd/compile/trace"
                   and s["args"]["program"] == program for s in inside)
    # The state is laid out before the init program is first called.
    layouts = [s for s in mine if s["name"] == "hvd/step/shardings"]
    first_init = min(s["start"] for s in calls
                     if s["args"]["kind"] == "init")
    assert layouts and layouts[0]["end"] <= first_init
    assert layouts[0]["args"] == {"program": "_init"}
    # No compile span of the later calls hangs under a first call, and
    # hundreds of steps add none.
    before = len(tl.spans())
    for _ in range(200):
        state, loss = step(step_fn, state)
    assert len([s for s in tl.spans()
                if s["name"] == "hvd/program/first_call"
                and s["start"] >= since]) == len(calls)
    assert len(tl.spans()) == before
