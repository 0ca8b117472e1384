"""The quickest proof that horovod_tpu still starts on the chip.

``python chip_smoke.py`` needs one TPU chip and drives the main path
once through the entry points a user would call:

* ``flash``   - the Pallas flash-attention kernels, compiled by Mosaic,
  against a float32 ``jax.numpy`` softmax attention at BERT-large's
  heads (8, 512, 16, 64), GPT-2's (4, 1024, 12, 64, causal) and the
  GPT cell's step (16, 1024, 16, 64, causal), all bf16: the forward's
  output, and dQ, dK, dV from the backward kernel against the
  reference's gradients;
* ``trainer`` - a trainer started by the launcher
  (``python -m horovod_tpu.runner.launch -np 1``) that calls
  ``hvd.init()``, a few eager ops on ``jax.Array`` inputs, builds its
  mesh with ``parallel.build_mesh`` and its step with
  ``training.make_bert_pretrain_step(bert_large_config(), mesh)`` and
  takes a warm-up and five steps at batch 64 x 128 on one fixed batch.

``python chip_smoke.py --chips 4`` needs the four chips of one host and
runs only what exists across chips, each beside what it is compared
with:

* ``sharded`` - one process, four chips: BERT-large and GPT-2 widths
  at depth 4 on a dp=2 x tp=2 mesh against the same seed and global
  batch on a one-device mesh; and, on one of the chips, the GPT step's
  own loss (over chunks of the sequence, never the logits) at
  gpt2-medium's widths and vocabulary against the benchmark's plain
  reference and against the logits path;
* ``eager``   - ``horovodrun -np 4``, one chip a process: the eager
  collectives of ``XlaMeshBackend`` on device arrays against numpy, and
  an MLP through ``hvd.jax.DistributedOptimizer`` against the same
  steps with an in-graph ``psum``.

The parent never touches JAX (a chip belongs to one process at a time):
every phase is a child, one after the other.  The last line of output
is one JSON object, ``{"ok": true, "device": {...}}`` with the device
as the children's JAX reported it.  Any failed phase, a child that
exits non-zero, or a device that is not a TPU gives ``"ok": false`` and
a non-zero exit code; nothing makes it pass on a CPU.  Times and sizes
on the earlier lines are information, not results.
"""

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
RESULT_MARK = "CHIP_SMOKE_RESULT "

# [B, S, H, D], causal: BERT-large's heads at S=512, GPT-2's at S=1024,
# what one step of the benchmark's GPT cell hands the kernels, and two
# sequences longer than a grid step holds (SEQ_BLOCK = 1024 rows): four
# blocks, causal, and one and a half, where the kernels' mask alone
# hides the padded half.
FLASH_CASES = (((8, 512, 16, 64), False), ((4, 1024, 12, 64), True),
               ((16, 1024, 16, 64), True), ((1, 4096, 16, 64), True),
               ((2, 1536, 16, 64), False))
# bf16 inputs and output against a float32 reference: the output's own
# rounding is 2^-9 relative, the kernel's P.V product runs on the MXU.
FLASH_ATOL = FLASH_RTOL = 2e-2
# dQ, dK, dV as relative L2 error of the whole array: each is rounded
# to bf16 on its way out (2^-9 an element, 1.1e-3 of the norm) and p
# and ds are rounded to bf16 before their second product, as the
# einsum path rounds its probabilities.  The v5e read 2.5e-3 to 2.8e-3
# at (16, 1024, 16, 64) causal and the einsum path 3.9e-3 to 4.4e-3
# (PERF.md, PR 25); fp8 operands (2^-4 a rounding) would read thirty
# times that.
FLASH_GRAD_REL_L2 = 1e-2
# Sharded against one device: same math, other reduction orders, bf16
# matmuls, three optimizer steps.
SHARDED_LOSS_RTOL = 2e-3
# The gradient leaves of the GPT step's loss that are held against the
# reference: the tied embedding (the head's and the lookup's gradients
# summed), a projection below every block, the last LayerNorm's scale.
GPT_CHECK_LEAVES = ("word_embeddings/embedding",
                    "layer_0/attention/query/kernel", "final_norm/scale")


def _info(msg: str):
    print("chip_smoke: " + msg, flush=True)


def _device(platform: str):
    """The first device, which must be of ``platform``."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != platform:
        raise RuntimeError("chip_smoke needs a %s device, JAX gave %s (%s)"
                           % (platform, dev.platform, dev.device_kind))
    return dev


def _result(phase: str, **extra):
    import jax
    dev = jax.devices()[0]
    print(RESULT_MARK + json.dumps({
        "phase": phase, "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())}, **extra}), flush=True)


def _on_platform(tree, platform: str) -> bool:
    import jax
    return all(d.platform == platform
               for leaf in jax.tree.leaves(tree) for d in leaf.devices())


def _peak_bytes(dev):
    return (dev.memory_stats() or {}).get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# phase: flash
# ---------------------------------------------------------------------------

def _softmax_attention_f32(q, k, v, causal: bool):
    """Plain float32 softmax attention on [B, S, H, D], independent of
    the kernel and of the package's own reference."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=hi)
    s = s / jnp.sqrt(jnp.float32(q.shape[-1]))
    if causal:
        keep = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))
        s = jnp.where(keep[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=hi)


def phase_flash(cases=FLASH_CASES, platform: str = "tpu",
                interpret: bool = False):
    import functools
    import jax
    import jax.numpy as jnp
    import numpy as np
    from horovod_tpu.common import compile_cache
    from horovod_tpu.ops.pallas_attention import flash_attention

    compile_cache.enable()
    _device(platform)
    for shape, causal in cases:
        keys = jax.random.split(jax.random.PRNGKey(0), 4)
        q, k, v = (jax.random.normal(kk, shape, jnp.bfloat16)
                   for kk in keys[:3])
        flash = functools.partial(flash_attention, causal=causal,
                                  interpret=interpret)
        out = jax.jit(flash)(q, k, v)
        ref = jax.jit(functools.partial(_softmax_attention_f32,
                                        causal=causal))(q, k, v)
        assert out.shape == shape and out.dtype == jnp.bfloat16, \
            (out.shape, out.dtype)
        assert _on_platform(out, platform)
        out32, ref = np.asarray(out, np.float32), np.asarray(ref)
        err = float(np.max(np.abs(out32 - ref)))
        np.testing.assert_allclose(out32, ref, atol=FLASH_ATOL,
                                   rtol=FLASH_RTOL)
        _info("flash forward %s causal=%s matches float32 reference: "
              "max abs err %.2e (atol %.0e rtol %.0e)"
              % (shape, causal, err, FLASH_ATOL, FLASH_RTOL))

        # The same cotangent through both; the reference's gradients
        # are float32 arrays of the inputs' shape.
        do = jax.random.normal(keys[3], shape, jnp.float32)

        def loss(f, q, k, v):
            return jnp.sum(f(q, k, v).astype(jnp.float32) * do)

        argnums = (0, 1, 2)
        grads = jax.jit(jax.grad(functools.partial(loss, flash),
                                 argnums))(q, k, v)
        wants = jax.jit(jax.grad(functools.partial(loss, functools.partial(
            _softmax_attention_f32, causal=causal)), argnums))(q, k, v)
        errs = {}
        for name, g, w in zip("qkv", grads, wants):
            assert g.shape == shape and g.dtype == jnp.bfloat16, \
                (name, g.shape, g.dtype)
            assert _on_platform(g, platform)
            g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
            errs[name] = float(np.linalg.norm(g - w) / np.linalg.norm(w))
            assert errs[name] <= FLASH_GRAD_REL_L2, \
                "flash d%s off the float32 reference: relative L2 %.2e " \
                "(bound %.0e)" % (name, errs[name], FLASH_GRAD_REL_L2)
        _info("flash backward %s causal=%s matches float32 reference: "
              "relative L2 dq %.2e dk %.2e dv %.2e (bound %.0e)"
              % (shape, causal, errs["q"], errs["k"], errs["v"],
                 FLASH_GRAD_REL_L2))
    _result("flash")


# ---------------------------------------------------------------------------
# phase: trainer (under the launcher, -np 1)
# ---------------------------------------------------------------------------

def phase_trainer(config=None, batch_size: int = 64, seq_len: int = 128,
                  steps: int = 5, platform: str = "tpu"):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu.common import basics
    from horovod_tpu.models.bert import bert_large_config
    from horovod_tpu.parallel import build_mesh
    from horovod_tpu.training import (make_bert_batch,
                                      make_bert_pretrain_step)

    config = config or bert_large_config()
    hvd.init()
    dev = _device(platform)
    _info("trainer: hvd.init() ok, rank %d of %d, backend %s, launched=%s"
          % (hvd.rank(), hvd.size(),
             type(basics._state().backend).__name__,
             basics._state().rank_info.launched))
    assert basics._state().rank_info.launched, \
        "the trainer phase runs under the launcher"

    x = jnp.arange(8, dtype=jnp.float32) + hvd.rank()
    want = sum(np.arange(8, dtype=np.float32) + r
               for r in range(hvd.size()))
    got = {
        "allreduce": hvd.allreduce(x, op=hvd.Sum, name="smoke.ar"),
        "broadcast": hvd.broadcast(x, root_rank=0, name="smoke.bc"),
        "allgather": hvd.allgather(x.reshape(2, 4), name="smoke.ag"),
    }
    assert all(isinstance(a, jax.Array) for a in got.values())
    assert _on_platform(got, platform)
    np.testing.assert_allclose(np.asarray(got["allreduce"]), want)
    np.testing.assert_allclose(np.asarray(got["broadcast"]),
                               np.arange(8, dtype=np.float32))
    assert got["allgather"].shape == (2 * hvd.size(), 4)
    _info("trainer: eager allreduce, broadcast, allgather ok on %s arrays"
          % platform)

    mesh = build_mesh()
    make_jitted, batch_sharding = make_bert_pretrain_step(config, mesh)
    batch = jax.tree.map(
        lambda a: jax.device_put(a, batch_sharding),
        make_bert_batch(batch_size, seq_len, config.vocab_size))
    init_fn, step_fn = make_jitted(batch)
    _info("trainer: BERT hidden %d, %d layers, %d heads, FFN %d, vocab %d,"
          " %s compute, AdamW, batch %d x %d, mesh %s"
          % (config.hidden_size, config.num_layers, config.num_heads,
             config.intermediate_size, config.vocab_size,
             jnp.dtype(config.dtype).name, batch_size, seq_len,
             dict(mesh.shape)))

    t0 = time.perf_counter()
    state = jax.block_until_ready(init_fn(jax.random.PRNGKey(0), batch))
    _info("trainer: init compiled and run in %.1f s"
          % (time.perf_counter() - t0))
    assert _on_platform(state, platform), "state is not on the " + platform
    n_params = sum(p.size for p in jax.tree.leaves(state.params))

    t0 = time.perf_counter()
    state, loss = step_fn(state, batch)
    losses = [float(jax.block_until_ready(loss))]
    _info("trainer: compile seconds (step, with its warm-up run): %.1f"
          % (time.perf_counter() - t0))
    times = []
    for _ in range(steps):
        t0 = time.perf_counter()
        state, loss = step_fn(state, batch)
        losses.append(float(jax.block_until_ready(loss)))
        times.append(time.perf_counter() - t0)
    _info("trainer: %d parameters; step seconds %s"
          % (n_params, ["%.4f" % t for t in times]))
    _info("trainer: losses on the repeated batch %s"
          % ["%.4f" % v for v in losses])
    _info("trainer: peak_bytes_in_use %s" % _peak_bytes(dev))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], \
        "loss did not fall on the repeated batch: %s" % losses
    assert _on_platform(state, platform)
    _result("trainer", steps=steps, layers=config.num_layers)
    hvd.shutdown()


# ---------------------------------------------------------------------------
# phase: sharded (one process, four chips)
# ---------------------------------------------------------------------------

def _check_sharded_state(state, tp_weight, compiled, devices):
    """Placement facts of a dp x tp state and its compiled step."""
    import jax
    import numpy as np
    used = {d for leaf in jax.tree.leaves(state) for d in leaf.devices()}
    assert used == set(devices), "state is on %s" % sorted(
        d.id for d in used)
    shards = {str(s.index): np.asarray(s.data)
              for s in tp_weight.addressable_shards}
    assert len(shards) == 2, "tensor-parallel weight has shards %s" % list(
        shards)
    a, b = shards.values()
    assert a.shape == b.shape and not np.array_equal(a, b)
    for d in devices[1:]:
        # The CPU backend of the rehearsals reports no memory_stats.
        assert d.platform != "tpu" or d.memory_stats()["bytes_in_use"], \
            "nothing resident on device %d" % d.id
    assert "all-reduce" in compiled.as_text(), \
        "the compiled sharded step holds no all-reduce"


def _bert_losses(config, mesh, batch_size, seq_len, steps, check):
    import jax
    from horovod_tpu.training import (make_bert_batch,
                                      make_bert_pretrain_step)
    make_jitted, batch_sharding = make_bert_pretrain_step(config, mesh)
    batch = jax.tree.map(
        lambda a: jax.device_put(a, batch_sharding),
        make_bert_batch(batch_size, seq_len, config.vocab_size))
    init_fn, step_fn = make_jitted(batch)
    state = init_fn(jax.random.PRNGKey(0), batch)
    compiled = step_fn.lower(state, batch).compile()
    if check:
        layer = state.params["encoder"]["layer_0"]
        _check_sharded_state(state, layer["intermediate"]["kernel"],
                             compiled, list(mesh.devices.flat))
    losses = []
    for _ in range(steps):
        state, loss = compiled(state, batch)
        losses.append(float(jax.block_until_ready(loss)))
    return losses


def _gpt_losses(config, mesh, batch_size, seq_len, steps, check):
    import jax
    from horovod_tpu.training import make_gpt_train_step
    init_fn, step_fn, batch_sharding = make_gpt_train_step(
        config, mesh, learning_rate=1e-4)
    ids = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(0), (batch_size, seq_len),
                           0, config.vocab_size), batch_sharding)
    params, opt_state = init_fn(jax.random.PRNGKey(1), ids)
    compiled = step_fn.lower(params, opt_state, ids).compile()
    if check:
        _check_sharded_state((params, opt_state),
                             params["layer_0"]["intermediate"]["kernel"],
                             compiled, list(mesh.devices.flat))
    losses = []
    for _ in range(steps):
        params, opt_state, loss = compiled(params, opt_state, ids)
        losses.append(float(jax.block_until_ready(loss)))
    return losses


def _gpt_step_loss_check(config, batch_size, seq_len):
    """The loss ``make_gpt_train_step`` differentiates and three of its
    gradient leaves, on one device: against the benchmark's plain
    float32 reference and against the same model's logits through
    ``lm_loss`` (bf16's rounding of the logits apart, the same
    arithmetic), both within the benchmark's tolerances.  On the chip
    the batch is sized to walk more than one chunk of the loss (4 x
    1024 tokens are two chunks of 512 positions; the info line says how
    many)."""
    import jax
    import numpy as np
    from benchmarks.reference import common as reference
    from benchmarks.reference import gpt as reference_gpt
    from horovod_tpu.models.gpt import GPTLMHeadModel, lm_loss
    from horovod_tpu.models.layers import loss_chunks
    from horovod_tpu.training import gpt_step_loss

    model = GPTLMHeadModel(config)
    ids = jax.random.randint(jax.random.PRNGKey(2), (batch_size, seq_len),
                             0, config.vocab_size)
    params = jax.jit(model.init)(jax.random.PRNGKey(3), ids)["params"]
    picked = {n: reference.get_leaf(params, n) for n in GPT_CHECK_LEAVES}

    def value_and_grad(loss):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda leaves: loss(reference.with_leaves(params, leaves))))(
                picked)
        return float(loss), {n: np.asarray(g, np.float32)
                             for n, g in grads.items()}

    got_loss, got = value_and_grad(lambda p: gpt_step_loss(model, p, ids))
    others = {"logits path": value_and_grad(lambda p: lm_loss(
        model.apply({"params": p}, ids), ids))}
    # The reference alone runs at the highest matmul precision (the
    # flash kernels take none).
    with jax.default_matmul_precision("highest"):
        others["reference"] = value_and_grad(lambda p: reference_gpt.loss(
            p, {"input_ids": ids},
            {"layer_norm_epsilon": config.layer_norm_eps,
             "n_layer": config.num_layers}))
    for other, (want_loss, want) in sorted(others.items()):
        loss_err = abs(got_loss - want_loss) / abs(want_loss)
        leaf_err = {n: float(np.linalg.norm(got[n] - want[n])
                             / np.linalg.norm(want[n])) for n in want}
        _info("sharded: gpt step loss %.6f, hidden %d, %d layers, "
              "vocabulary %d, batch %d x %d in %d chunks of %d positions, "
              "against the %s, %.6f: "
              "relative error %.2e (limit %.0e), leaves %s (limit %.0e)"
              % (got_loss, config.hidden_size, config.num_layers,
                 config.vocab_size, batch_size, seq_len,
                 *loss_chunks(seq_len, batch_size), other, want_loss,
                 loss_err, reference.LOSS_RTOL, json.dumps(leaf_err),
                 reference.GRAD_REL_L2))
        assert loss_err <= reference.LOSS_RTOL, other
        assert max(leaf_err.values()) <= reference.GRAD_REL_L2, other


def phase_sharded(bert_config=None, gpt_config=None,
                  bert_batch=(64, 128), gpt_batch=(8, 512),
                  gpt_check_config=None, gpt_check_batch=(4, 1024),
                  steps: int = 3, platform: str = "tpu"):
    import jax
    import numpy as np
    from horovod_tpu.common import compile_cache
    from horovod_tpu.models.bert import bert_large_config
    from horovod_tpu.models.gpt import gpt2_medium_config, gpt2_small_config
    from horovod_tpu.parallel import build_mesh

    compile_cache.enable()
    _device(platform)
    devices = jax.devices()[:4]
    assert len(devices) == 4, "the sharded phase needs four devices, " \
        "JAX gave %d" % len(jax.devices())
    bert_config = bert_config or bert_large_config(
        num_layers=4, hidden_dropout=0., attention_dropout=0.)
    gpt_config = gpt_config or gpt2_small_config(num_layers=4, dropout=0.)
    four = build_mesh({"dp": 2, "tp": 2}, devices)
    one = build_mesh({"dp": 1, "tp": 1}, devices[:1])
    for name, fn, config, (b, s) in (
            ("bert", _bert_losses, bert_config, bert_batch),
            ("gpt", _gpt_losses, gpt_config, gpt_batch)):
        sharded = fn(config, four, b, s, steps, check=True)
        single = fn(config, one, b, s, steps, check=False)
        _info("sharded: %s hidden %d, %d layers, batch %d x %d: losses on"
              " dp=2 x tp=2 %s, on one device %s (rtol %.0e)"
              % (name, config.hidden_size, config.num_layers, b, s,
                 ["%.4f" % v for v in sharded],
                 ["%.4f" % v for v in single], SHARDED_LOSS_RTOL))
        assert np.isfinite(sharded).all() and np.isfinite(single).all()
        np.testing.assert_allclose(sharded, single,
                                   rtol=SHARDED_LOSS_RTOL)
        _info("sharded: %s state on four devices, a tensor-parallel weight"
              " in two distinct shards, all-reduce in the compiled step"
              % name)
    _gpt_step_loss_check(
        gpt_check_config or gpt2_medium_config(num_layers=2, dropout=0.,
                                               remat=True),
        *gpt_check_batch)
    _result("sharded")


# ---------------------------------------------------------------------------
# phase: eager (under the launcher, -np 4, one chip a process)
# ---------------------------------------------------------------------------

def _eager_ops(hvd, platform):
    """allreduce (Sum, Average), grouped allreduce, uneven allgather,
    broadcast, alltoall with splits and reducescatter on device arrays,
    each against its numpy expectation."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    rank, size = hvd.rank(), hvd.size()

    def dev(a):
        a = jnp.asarray(a)
        assert _on_platform(a, platform)
        return a

    def host(a):
        assert isinstance(a, jax.Array) and _on_platform(a, platform), \
            type(a)
        return np.asarray(a)

    base = np.arange(10, dtype=np.float32)
    total = sum(base + 100.0 * r for r in range(size))
    x = dev(base + 100.0 * rank)
    np.testing.assert_allclose(
        host(hvd.allreduce(x, op=hvd.Sum, name="eg.sum")), total)
    np.testing.assert_allclose(
        host(hvd.allreduce(x, op=hvd.Average, name="eg.avg")),
        total / size, rtol=1e-6)

    outs = hvd.grouped_allreduce(
        [dev(np.full(3, 1.0 + rank, np.float32)),
         dev(np.full(5, 2.0 + rank, np.float32))],
        op=hvd.Sum, name="eg.grouped")
    for out, n, first in zip(outs, (3, 5), (1.0, 2.0)):
        np.testing.assert_allclose(
            host(out), np.full(n, sum(first + r for r in range(size))))

    g = host(hvd.allgather(
        dev(np.full((rank + 1, 2), float(rank), np.float32)),
        name="eg.ag"))
    assert g.shape == (size * (size + 1) // 2, 2), g.shape
    np.testing.assert_allclose(
        g[:, 0], np.repeat(np.arange(size), np.arange(size) + 1))

    np.testing.assert_allclose(
        host(hvd.broadcast(x, root_rank=size - 1, name="eg.bc")),
        base + 100.0 * (size - 1))

    # Rank r sends (r + 2j) % 3 + 1 rows to rank j, each row tagged
    # with its sender and its position in the sender's buffer.
    def sends(r):
        return np.array([(r + 2 * j) % 3 + 1 for j in range(size)])

    def buffer(r):
        return 1000.0 * r + np.arange(sends(r).sum(), dtype=np.float32)

    y, recv = hvd.alltoall(dev(buffer(rank)), splits=sends(rank),
                           name="eg.a2a")
    want, want_splits = [], []
    for r in range(size):
        start = sends(r)[:rank].sum()
        want.append(buffer(r)[start:start + sends(r)[rank]])
        want_splits.append(sends(r)[rank])
    np.testing.assert_allclose(host(y), np.concatenate(want))
    np.testing.assert_array_equal(np.asarray(recv), want_splits)

    rows = np.tile(np.arange(size * 2, dtype=np.float32)[:, None], (1, 3))
    rs = host(hvd.reducescatter(dev(rows + rank), op=hvd.Sum,
                                name="eg.rs"))
    np.testing.assert_allclose(
        rs, (rows * size + sum(range(size)))[rank * 2:(rank + 1) * 2])


def _eager_mlp(hvd, platform, steps: int = 4):
    """A small MLP trained twice from one seed on per-rank data: grads
    through hvd.jax.DistributedOptimizer (the eager plane) and through
    an in-graph psum over the same chips; parameters must agree."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    import horovod_tpu.jax as hj
    from jax.sharding import NamedSharding, PartitionSpec as P
    from horovod_tpu.parallel import build_mesh

    rank, size = hvd.rank(), hvd.size()
    rng = np.random.RandomState(7)
    params = {"w1": jnp.asarray(rng.randn(16, 32).astype(np.float32) * .3),
              "b1": jnp.zeros(32, jnp.float32),
              "w2": jnp.asarray(rng.randn(32, 4).astype(np.float32) * .3)}
    data = np.random.RandomState(100 + rank)
    x = jnp.asarray(data.randn(8, 16).astype(np.float32))
    y = jnp.asarray(data.randn(8, 4).astype(np.float32))

    def loss_fn(p, x, y):
        h = jnp.tanh(x @ p["w1"] + p["b1"])
        return jnp.mean((h @ p["w2"] - y) ** 2)

    tx = optax.sgd(0.1, momentum=0.9)

    eager_tx = hj.DistributedOptimizer(tx)
    p, opt = params, eager_tx.init(params)
    grad = jax.jit(jax.grad(loss_fn))
    for _ in range(steps):
        updates, opt = eager_tx.update(grad(p, x, y), opt, p)
        p = optax.apply_updates(p, updates)
    eager = jax.tree.map(np.asarray, p)

    mesh = build_mesh({"dp": size})

    def per_chip(p, opt, x, y):
        # Differentiate a per-chip copy of the replicated parameters, so
        # that the reduction is the psum written here and not one the
        # transpose of the replication would add.
        local = jax.tree.map(
            lambda a: jax.lax.pcast(a, "dp", to="varying"), p)
        g = jax.tree.map(lambda a: jax.lax.psum(a, "dp") / size,
                         jax.grad(loss_fn)(local, x, y))
        updates, opt = tx.update(g, opt, p)
        return optax.apply_updates(p, updates), opt

    step = jax.jit(jax.shard_map(
        per_chip, mesh=mesh, in_specs=(P(), P(), P("dp"), P("dp")),
        out_specs=(P(), P())))
    repl = NamedSharding(mesh, P())
    p = jax.tree.map(
        lambda a: jax.make_array_from_process_local_data(repl,
                                                         np.asarray(a)),
        params)
    opt = jax.jit(tx.init, out_shardings=repl)(p)
    gx, gy = (jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("dp")), np.asarray(a)) for a in (x, y))
    for _ in range(steps):
        p, opt = step(p, opt, gx, gy)
    assert _on_platform(p, platform)
    for name, want in eager.items():
        np.testing.assert_allclose(
            np.asarray(p[name].addressable_data(0)), want,
            rtol=1e-4, atol=1e-5, err_msg=name)


def phase_eager(platform: str = "tpu"):
    import jax
    import horovod_tpu as hvd
    from horovod_tpu.common import basics

    hvd.init()
    _device(platform)
    backend = type(basics._state().backend).__name__
    assert hvd.size() > 1 and backend == "XlaMeshBackend", \
        (hvd.size(), backend)
    assert jax.process_count() == hvd.size() and \
        jax.local_device_count() == 1, \
        "rank %d holds %d of %d devices" % (
            hvd.rank(), jax.local_device_count(), jax.device_count())
    _eager_ops(hvd, platform)
    _info("eager: rank %d of %d on %s: allreduce, grouped allreduce, "
          "allgather, broadcast, alltoall, reducescatter equal numpy"
          % (hvd.rank(), hvd.size(), jax.local_devices()[0]))
    _eager_mlp(hvd, platform)
    _info("eager: rank %d: MLP through DistributedOptimizer equals the "
          "in-graph psum" % hvd.rank())
    # Before shutdown, while JAX still sees every rank's device; a
    # shutdown that fails still fails the phase by its exit code.
    _result("eager", ranks=hvd.size())
    hvd.shutdown()


PHASES = {"flash": phase_flash, "trainer": phase_trainer,
          "sharded": phase_sharded, "eager": phase_eager}


# ---------------------------------------------------------------------------
# parent: no JAX here
# ---------------------------------------------------------------------------

def _child_argv(phase: str, np: int = 0):
    child = [sys.executable, os.path.join(HERE, "chip_smoke.py"),
             "--phase", phase]
    if not np:
        return child
    return [sys.executable, "-m", "horovod_tpu.runner.launch",
            "-np", str(np)] + child


def run_phase(phase: str, np: int, want_results: int, timeout_s: float):
    """Run one phase as a child (under the launcher when ``np``), echo
    its output, and return the device its result lines report, or None
    if it failed.  A child still running after ``timeout_s`` is killed
    with everything it started."""
    argv = _child_argv(phase, np)
    _info("phase %s: starting %s" % (phase, " ".join(argv)))
    t0 = time.monotonic()
    proc = subprocess.Popen(argv, cwd=HERE, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True,
                            start_new_session=True)
    results = []
    timer = threading.Timer(timeout_s, _kill_group, args=(proc,))
    timer.daemon = True
    timer.start()
    try:
        for line in proc.stdout:
            sys.stdout.write(line)
            sys.stdout.flush()
            if RESULT_MARK in line:
                results.append(json.loads(line.split(RESULT_MARK, 1)[1]))
        rc = proc.wait()
    finally:
        timer.cancel()
        _kill_group(proc)
    ok = rc == 0 and len(results) == want_results and \
        all(r.get("ok") and r.get("phase") == phase for r in results)
    _info("phase %s: %s (exit code %s, %d result lines, %.0f s)"
          % (phase, "ok" if ok else "FAILED", rc, len(results),
             time.monotonic() - t0))
    return results[0]["device"] if ok else None


def _kill_group(proc):
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip main path (default); 4: only "
                         "the paths that exist across four chips")
    ap.add_argument("--phase", choices=sorted(PHASES),
                    help="run one phase in this process (what the "
                         "parent starts as a child)")
    args = ap.parse_args()
    if args.phase:
        PHASES[args.phase]()
        return 0

    # (phase, launcher -np or 0 for a plain child, result lines, limit in
    # seconds: several times what the phase took on a v5e, cold).
    if args.chips == 1:
        plan = [("flash", 0, 1, 300), ("trainer", 1, 1, 850)]
    else:
        plan = [("sharded", 0, 1, 600), ("eager", 4, 4, 300)]
    devices, failed = [], []
    for phase, np, want_results, timeout_s in plan:
        dev = run_phase(phase, np, want_results, timeout_s)
        if dev is None:
            failed.append(phase)
        else:
            devices.append(dev)
    device = devices[0] if devices else None
    ok = not failed and all(
        d == device and d["platform"] == "tpu" for d in devices)
    if ok:
        print(json.dumps({"ok": True, "device": device}))
        return 0
    print(json.dumps({"ok": False, "device": device, "failed": failed}))
    return 1


if __name__ == "__main__":
    sys.exit(main())
