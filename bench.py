"""Headline benchmarks: ResNet-50 img/sec, BERT-large samples/sec, MFU,
and an eager-path allreduce micro-benchmark.

Covers both halves of the BASELINE headline metric ("ResNet-50
images/sec/chip; BERT-large samples/sec") plus:

- ResNet-50 synthetic training throughput (reference:
  examples/tensorflow2/tensorflow2_synthetic_benchmark.py,
  examples/pytorch/pytorch_synthetic_benchmark.py:106-118 — metric:
  img/sec = batch_size * num_batches_per_iter / time).
- BERT-large MLM training samples/sec (reference: examples/adasum/,
  docs/adasum_user_guide.rst — the Adasum BERT-large baseline config).
- MFU for both, from XLA's compiled cost analysis (fallback: analytic
  matmul FLOP count) over the chip's peak bf16 FLOP/s.
- A collectives micro-bench that drives ``hvd.allreduce`` through the
  REAL eager data plane across 2 worker processes (jax.Array and numpy
  inputs, 1–256 MB), reporting GB/s and control-frame counts so the
  response-cache fast path and device-resident staging show up in a
  driver-captured number.

``vs_baseline`` keeps its round-1/2 definition (ResNet img/sec/device
over the reference's only published absolute number: ResNet-101,
tf_cnn_benchmarks, 1656.82 img/sec on 16 P100s, docs/benchmarks.rst:
32-43); MFU sits next to it as the honest hardware-relative number.

``python bench.py`` measures on the TPU and fails without one: it exits
non-zero when ``jax.devices()[0].platform`` is not ``"tpu"``, and when
any lane it ran reported an error.  ``--smoke`` is the CPU variant (tiny
shapes, for CI); every output names the ``platform`` it ran on.

Prints exactly ONE JSON line.
"""

import argparse
import json
import os
import subprocess
import sys
import time

REFERENCE_IMG_SEC_PER_DEVICE = 1656.82 / 16  # docs/benchmarks.rst:32-43

# Peak dense bf16 TFLOP/s per chip, keyed on substrings of
# jax.Device.device_kind (public cloud.google.com/tpu/docs numbers).
# A kind that is not listed is an error, not a default.
PEAK_BF16_TFLOPS = [
    ("v6e", 918.0), ("v6", 918.0),
    ("v5p", 459.0),
    ("v5e", 197.0), ("v5litepod", 197.0), ("v5 lite", 197.0),
    ("v4", 275.0),
    ("v3", 123.0),
    ("v2", 45.0),
]


def peak_bf16_tflops(device) -> float:
    kind = device.device_kind.lower()
    for key, tf in PEAK_BF16_TFLOPS:
        if key in kind:
            return tf
    raise ValueError(
        "no peak bf16 TFLOP/s listed for device_kind %r; add it to "
        "PEAK_BF16_TFLOPS with its source" % device.device_kind)


def compiled_flops(jitted, *args):
    """Per-call FLOPs from XLA's cost analysis; 0.0 if unavailable."""
    try:
        ca = jitted.lower(*args).compile().cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        return float(ca.get("flops", 0.0))
    except Exception:
        return 0.0


def _timed_loop(step, carry, warmup, iters, fetch_scalar):
    """Run warmup + timed iterations of ``carry = step(carry)``; the
    host-side scalar fetch is the execution barrier.  Timed in up to 5
    chunks so the artifact can report scheduler-noise spread next to
    the headline number (on the 1-core rig a single long loop hides
    ±15% swings).  Returns
    (total_seconds, {"spread_pct", "chunk_iters_per_sec"})."""
    for _ in range(warmup):
        carry = step(carry)
    fetch_scalar(carry)
    iters = max(iters, 1)
    nchunks = min(5, iters)
    per = iters // nchunks
    rates, total = [], 0.0
    left = iters
    for c in range(nchunks):
        k = per if c < nchunks - 1 else left
        t0 = time.perf_counter()
        for _ in range(k):
            carry = step(carry)
        fetch_scalar(carry)
        dt = time.perf_counter() - t0
        total += dt
        rates.append(k / dt)
        left -= k
    spread = ((max(rates) - min(rates)) / (sum(rates) / len(rates))
              * 100 if len(rates) > 1 else 0.0)
    return total, {"spread_pct": round(spread, 1),
                   "chunk_iters_per_sec": [round(r, 2) for r in rates]}


# ---------------------------------------------------------------------------
# ResNet-50 synthetic training benchmark
# ---------------------------------------------------------------------------

def build_resnet_train_step(batch_size: int, image_size: int,
                            num_classes: int, smoke: bool = False):
    """The benchmark train step, shared with tools/profile_resnet.py
    so the profiler measures EXACTLY the program the benchmark runs.
    Returns (train_step, params, batch_stats, opt_state, x, labels)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from functools import partial

    from horovod_tpu.models import ResNet50, ResNet18

    model = (ResNet18 if smoke else ResNet50)(num_classes=num_classes)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(batch_size, image_size, image_size, 3),
                    dtype=jnp.bfloat16)
    labels = jnp.asarray(rng.randint(0, num_classes, batch_size),
                         dtype=jnp.int32)
    # Jit the init: unjitted flax init runs the forward op-by-op on
    # the default device.  One compiled program instead.
    variables = jax.jit(lambda r, xx: model.init(r, xx, train=True))(
        jax.random.PRNGKey(0), x)
    params, batch_stats = variables["params"], variables["batch_stats"]
    tx = optax.sgd(0.01, momentum=0.9)
    opt_state = tx.init(params)

    def loss_fn(params, batch_stats, x, labels):
        logits, updates = model.apply(
            {"params": params, "batch_stats": batch_stats}, x,
            train=True, mutable=["batch_stats"])
        logp = jax.nn.log_softmax(logits)
        loss = -jnp.take_along_axis(logp, labels[:, None],
                                    axis=-1).mean()
        return loss, updates["batch_stats"]

    # Donation lets XLA update params/opt state in place (no HBM copies
    # per step — the analog of the reference's fusion-buffer reuse).
    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def train_step(params, batch_stats, opt_state, x, labels):
        (loss, new_bs), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch_stats, x, labels)
        updates, new_opt = tx.update(grads, opt_state, params)
        new_params = optax.apply_updates(params, updates)
        return new_params, new_bs, new_opt, loss

    return train_step, params, batch_stats, opt_state, x, labels


def resnet50_analytic_flops(batch_size: int) -> float:
    """ResNet-50 fwd ≈ 4.1 GFLOPs/image at 224²; training ≈ 3× fwd."""
    return 3 * 4.1e9 * batch_size


def bench_resnet(args, smoke: bool) -> dict:
    import jax

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if smoke:
        batch_size, img, iters, warmup = args.batch_size or 8, 32, 5, 2
    else:
        batch_size = args.batch_size or (128 if on_tpu else 16)
        img, iters, warmup = 224, args.num_iters, args.warmup

    (train_step, params, batch_stats, opt_state, x,
     labels) = build_resnet_train_step(
        batch_size, img, 10 if smoke else 1000, smoke=smoke)

    step_flops = compiled_flops(train_step, params, batch_stats, opt_state,
                                x, labels)
    if not step_flops and not smoke:
        step_flops = resnet50_analytic_flops(batch_size)

    # Opt-in per-HLO profile (HOROVOD_BENCH_PROFILE=1): the MFU-ceiling
    # analysis (bytes accessed, implied HBM-bound step time, transpose/
    # copy histogram) lands in THIS artifact instead of resting on
    # earlier rounds' prose.  Must run before the timed loop: the loop
    # donates params/opt_state away.
    profile = None
    if os.environ.get("HOROVOD_BENCH_PROFILE") == "1":
        try:
            sys.path.insert(0, os.path.join(os.path.dirname(
                os.path.abspath(__file__)), "tools"))
            from profile_resnet import compiled_step_summary
            profile = compiled_step_summary(
                train_step, (params, batch_stats, opt_state, x, labels),
                dev, 0.0 if smoke else
                resnet50_analytic_flops(batch_size))
        except Exception as e:
            profile = {"error": repr(e)[:300]}

    dt, noise = _timed_loop(
        lambda c: train_step(c[0], c[1], c[2], x, labels),
        (params, batch_stats, opt_state, None), warmup, iters,
        lambda c: float(c[3]))
    img_sec = batch_size * iters / dt
    peak = None if smoke else peak_bf16_tflops(dev)
    out = {
        "images_per_sec": round(img_sec, 2),
        "batch_size": batch_size,
        "spread_pct": noise["spread_pct"],
        "mfu": round(step_flops * iters / dt / (peak * 1e12), 4)
               if peak and step_flops else None,
        "tflops_per_sec": round(step_flops * iters / dt / 1e12, 2)
                          if step_flops else None,
    }
    if profile is not None:
        out["profile"] = profile
    return out


# ---------------------------------------------------------------------------
# BERT-large MLM training benchmark
# ---------------------------------------------------------------------------

def bench_bert(args, smoke: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from functools import partial

    from horovod_tpu.models import (BertForMaskedLM, bert_large_config,
                                    bert_tiny_config, mlm_loss)

    dev = jax.devices()[0]
    if smoke:
        cfg = bert_tiny_config()
        batch, seq, iters, warmup = 4, 32, 3, 1
    else:
        cfg = bert_large_config()
        batch = args.bert_batch
        seq = args.bert_seq
        iters, warmup = max(args.num_iters // 2, 10), args.warmup

    model = BertForMaskedLM(cfg)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)),
                      dtype=jnp.int32)
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)),
                         dtype=jnp.int32)
    # 15% MLM masking, the BERT pretraining rate.
    mask = jnp.asarray(rng.rand(batch, seq) < 0.15, dtype=jnp.int32)

    params = jax.jit(model.init)(jax.random.PRNGKey(0), ids)["params"]
    tx = optax.adamw(1e-4, weight_decay=0.01)
    opt_state = tx.init(params)

    def loss_fn(params, ids, labels, mask):
        logits = model.apply({"params": params}, ids)
        return mlm_loss(logits, labels, mask)

    @partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state, ids, labels, mask):
        loss, grads = jax.value_and_grad(loss_fn)(params, ids, labels, mask)
        updates, new_opt = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_opt, loss

    step_flops = compiled_flops(train_step, params, opt_state, ids, labels,
                                mask)
    if not step_flops:
        # Analytic matmul count: per token per layer, fwd =
        # 2·12h² (qkv/out/ffn weights) + 4·s·h (QKᵀ and AV), plus the
        # 2·h·V LM head; training ≈ 3× fwd.
        h, L, s, V = (cfg.hidden_size, cfg.num_layers, seq, cfg.vocab_size)
        tokens = batch * seq
        step_flops = 3 * (tokens * L * (24 * h * h + 4 * s * h)
                          + tokens * 2 * h * V)

    dt, noise = _timed_loop(
        lambda c: train_step(c[0], c[1], ids, labels, mask),
        (params, opt_state, None), warmup, iters,
        lambda c: float(c[2]))
    peak = None if smoke else peak_bf16_tflops(dev)
    return {
        "samples_per_sec": round(batch * iters / dt, 2),
        "batch_size": batch,
        "seq_len": seq,
        "spread_pct": noise["spread_pct"],
        "mfu": round(step_flops * iters / dt / (peak * 1e12), 4)
               if peak and step_flops else None,
        "tflops_per_sec": round(step_flops * iters / dt / 1e12, 2)
                          if step_flops else None,
    }


# ---------------------------------------------------------------------------
# Keras-on-JAX training benchmark (the Keras TPU story: compute inside
# keras's jit-compiled jax train step; reference config keras_mnist.py)
# ---------------------------------------------------------------------------

def bench_keras_jax(args, smoke: bool) -> dict:
    os.environ.setdefault("KERAS_BACKEND", "jax")
    import keras
    if keras.backend.backend() != "jax":
        return {"error": "keras backend is %r (KERAS_BACKEND was set "
                         "after keras import?)" % keras.backend.backend()}
    import numpy as np
    import horovod_tpu.keras as hvd

    # Elastic knob forces the gradient-sync callback to be BAKED into
    # the compiled step even at size 1 (a resizable world may grow), so
    # the sync-vs-plain delta below isolates exactly the per-step
    # io_callback hop the eager plane pays (VERDICT r4 item 4).  The
    # knob only matters at init; restore the env immediately so later
    # bench sections (collectives workers inherit os.environ) don't
    # silently run elastic-mode controllers.
    had_elastic = os.environ.get("HOROVOD_ELASTIC")
    os.environ["HOROVOD_ELASTIC"] = had_elastic or "1"
    try:
        hvd.init()
    finally:
        if had_elastic is None:
            os.environ.pop("HOROVOD_ELASTIC", None)
    if smoke:
        batch, n = 64, 1024
        model = keras.Sequential([
            keras.layers.Input((28, 28, 1)), keras.layers.Flatten(),
            keras.layers.Dense(64, activation="relu"),
            keras.layers.Dense(10, activation="softmax")])
    else:
        batch, n = args.batch_size or 128, 16384
        model = keras.Sequential([
            keras.layers.Input((28, 28, 1)),
            keras.layers.Conv2D(32, 3, activation="relu"),
            keras.layers.MaxPooling2D(),
            keras.layers.Conv2D(64, 3, activation="relu"),
            keras.layers.MaxPooling2D(),
            keras.layers.Flatten(),
            keras.layers.Dense(128, activation="relu"),
            keras.layers.Dense(10, activation="softmax")])
    rng = np.random.RandomState(0)
    x = rng.rand(n, 28, 28, 1).astype("float32")
    y = rng.randint(0, 10, n)
    opt = hvd.DistributedOptimizer(keras.optimizers.Adam(1e-3))
    model.compile(optimizer=opt,
                  loss="sparse_categorical_crossentropy")
    model.fit(x, y, batch_size=batch, epochs=1, verbose=0)  # compile
    t0 = time.perf_counter()
    model.fit(x, y, batch_size=batch, epochs=1, verbose=0)
    dt = time.perf_counter() - t0
    dev = {d.platform for v in model.trainable_variables
           for d in v.value.devices()}

    # Same architecture/data with a PLAIN optimizer: the delta is the
    # cost of suspending the compiled step into the eager collective
    # plane (io_callback + host staging + loopback reduce) per step.
    # (clone_model would try to serialize the dynamic Distributed*
    # optimizer class; a fresh build times identically.)
    def rebuild():
        return keras.models.Sequential(
            [keras.layers.Input((28, 28, 1))]
            + [type(l).from_config(l.get_config())
               for l in model.layers])

    plain = rebuild()
    plain.compile(optimizer=keras.optimizers.Adam(1e-3),
                  loss="sparse_categorical_crossentropy")
    plain.fit(x, y, batch_size=batch, epochs=1, verbose=0)  # compile
    t0 = time.perf_counter()
    plain.fit(x, y, batch_size=batch, epochs=1, verbose=0)
    dt_plain = time.perf_counter() - t0

    out = {
        "samples_per_sec": round(n / dt, 2),
        "batch_size": batch,
        "backend": "jax",
        "param_device": sorted(dev),
        "plain_samples_per_sec": round(n / dt_plain, 2),
        "iocb_sync_overhead_pct": round((dt - dt_plain) / dt_plain
                                        * 100, 1),
    }

    # In-graph plane (hvd.keras.set_data_parallel): gradient sync is
    # compiled into the SPMD step — no io_callback, no host staging.
    try:
        import jax
        from keras import distribution as kd
        hvd.set_data_parallel(seed=0)
        spmd = rebuild()
        spmd.compile(
            optimizer=hvd.DistributedOptimizer(
                keras.optimizers.Adam(1e-3)),
            loss="sparse_categorical_crossentropy")
        # The distributed trainer finishes compiling on the SECOND
        # epoch (epoch-boundary retrace); warm both before timing.
        spmd.fit(x, y, batch_size=batch, epochs=2, verbose=0)
        t0 = time.perf_counter()
        spmd.fit(x, y, batch_size=batch, epochs=1, verbose=0)
        dt_spmd = time.perf_counter() - t0
        out["spmd_samples_per_sec"] = round(n / dt_spmd, 2)
        out["spmd_devices"] = len(jax.devices())
        if len(jax.devices()) == 1:
            # Only comparable to `plain` on one device: with several,
            # the SPMD model shards the batch over all of them while
            # plain uses one — the delta would be speedup, not sync
            # overhead.
            out["spmd_sync_overhead_pct"] = round(
                (dt_spmd - dt_plain) / dt_plain * 100, 1)
    except Exception as e:
        out["spmd_error"] = repr(e)[:300]
    finally:
        try:
            kd.set_distribution(None)
        except Exception:
            pass
    return out


# ---------------------------------------------------------------------------
# Async durable-checkpoint overhead (vs no-checkpoint baseline)
# ---------------------------------------------------------------------------

def bench_checkpoint(args, smoke: bool) -> dict:
    """Async-checkpoint overhead on the CPU smoke trainer: the smoke
    ResNet train step timed bare vs with durable async commits
    (horovod_tpu.checkpoint pipeline — host capture on the step path;
    shard write, fsync, two-phase manifest publish, retention GC on
    the writer thread), plus restore latency for the result.

    The commit cadence is DERIVED the CheckFreq way: one measured
    synchronous save fixes the per-checkpoint cost, and the cadence is
    chosen so the amortized cost targets < 5 % of the baseline step
    time (on a 1-core rig the persistence CPU cannot hide behind
    training, so cadence is the only lever — exactly the CheckFreq
    argument; the artifact records the cadence, the blocking capture
    cost, and the wall overhead separately)."""
    import math
    import shutil
    import tempfile

    import jax
    import numpy as np

    from horovod_tpu.checkpoint import CheckpointManager
    from horovod_tpu.common import metrics as _metrics

    if smoke:
        batch_size, img, iters, warmup = args.batch_size or 8, 32, 10, 2
    else:
        batch_size = args.batch_size or 16
        img, iters, warmup = 224, max(args.num_iters // 2, 10), \
            args.warmup
    (train_step, params, batch_stats, opt_state, x,
     labels) = build_resnet_train_step(batch_size, img, 10, smoke=True)

    def step(c):
        return train_step(c[0], c[1], c[2], x, labels)

    def fresh_carry():
        # train_step donates its carry; each timed phase needs its own
        # copy of the initial state or the second phase would feed
        # already-donated buffers.
        return jax.tree_util.tree_map(
            lambda a: a.copy(), (params, batch_stats, opt_state)
        ) + (None,)

    def snapshot_items(c):
        # np.array (not asarray): a forced host copy — a zero-copy
        # view would alias a buffer the next step donates away while
        # the writer thread is still serializing it.
        leaves = jax.tree_util.tree_leaves((c[0], c[1], c[2]))
        return {"leaf/%05d" % i: np.array(l)
                for i, l in enumerate(leaves)}

    dt_base, noise_base = _timed_loop(step, fresh_carry(), warmup,
                                      iters, lambda c: float(c[3]))
    step_s = dt_base / iters

    ckpt_dir = tempfile.mkdtemp(prefix="hvd-bench-ckpt-")
    mgr = CheckpointManager(ckpt_dir, keep=2)
    try:
        # One synchronous probe save fixes the per-checkpoint cost,
        # from which the cadence that amortizes to the 5% target
        # falls out (CheckFreq's tuning rule).  The measured loop runs
        # at a CAPPED cadence so the smoke actually contains several
        # saves — a deliberate over-stress on rigs where the derived
        # cadence is long; `amortized_overhead_pct` (below) is the
        # number the target applies to.
        t0 = time.perf_counter()
        mgr.save(0, snapshot_items(fresh_carry()), timeout=120)
        save_probe_s = time.perf_counter() - t0
        derived_cadence = max(1, int(math.ceil(
            save_probe_s / (0.05 * step_s))))
        cadence = min(derived_cadence, 25)
        iters_ckpt = max(iters, min(2 * cadence, 50))

        counter = {"step": 0}

        def step_ckpt(c):
            c = train_step(c[0], c[1], c[2], x, labels)
            counter["step"] += 1
            if counter["step"] % cadence == 0:
                # Host-side capture on the training path; everything
                # after (serialize/fsync/commit) rides the writer.
                mgr.save_async(counter["step"], snapshot_items(c))
            return c

        dt_ckpt, noise_ckpt = _timed_loop(
            step_ckpt, fresh_carry(), warmup, iters_ckpt,
            lambda c: float(c[3]))
        if not mgr.wait(timeout=120):
            return {"error": "checkpoint writer never drained"}
        saves = counter["step"] // cadence

        t0 = time.perf_counter()
        restored_step, items = mgr.restore_latest()
        restore_s = time.perf_counter() - t0
        flat = snapshot_items(fresh_carry())   # shape/coverage check
        nbytes = sum(v.nbytes for v in items.values())

        snap = _metrics.snapshot()
        save_hist = snap.get("histograms", {}).get(
            "hvd_ckpt_save_seconds", {})
        total = save_hist.get("phase=total", {})
        capture = save_hist.get("phase=capture", {})
        overhead_pct = (dt_ckpt / iters_ckpt - step_s) / step_s * 100.0
        capture_pct = (capture["sum"] / dt_ckpt * 100.0) \
            if capture.get("count") else None
        # Per-save cost for the cadence rule: the writer's own busy
        # time (serialize+write+commit, measured in-loop) — on 1 core
        # a zero-overlap UPPER bound on what a save can add to the
        # run, and far more stable than the wall delta on a noisy rig
        # (the wall-measured `overhead_pct` stays as the empirical
        # cross-check).  `cadence_for_target` is the
        # HOROVOD_CHECKPOINT_EVERY an operator sets to bound overhead
        # at 5% even with zero overlap; `amortized_overhead_pct` is
        # the bound actually achieved at that cadence.
        save_cost_s = (total["sum"] / total["count"]) \
            if total.get("count") else save_probe_s
        cadence_for_target = max(1, int(math.ceil(
            save_cost_s / (0.05 * step_s))))
        amortized_pct = save_cost_s / (cadence_for_target *
                                       step_s) * 100.0
        return {
            "steps": iters_ckpt,
            "cores": os.cpu_count(),
            "baseline_steps_per_sec": round(iters / dt_base, 2),
            "ckpt_steps_per_sec": round(iters_ckpt / dt_ckpt, 2),
            "cadence_steps": cadence,
            "derived_cadence_steps": derived_cadence,
            "saves": saves,
            "overhead_pct": round(overhead_pct, 1),
            "save_cost_ms": round(save_cost_s * 1e3, 1),
            "cadence_for_target": cadence_for_target,
            "amortized_overhead_pct": round(amortized_pct, 2),
            "overhead_target_pct": 5.0,
            # What the training thread pays synchronously (the
            # CheckFreq decoupling claim, cadence-independent).
            "capture_overhead_pct": round(capture_pct, 3)
            if capture_pct is not None else None,
            "spread_pct": max(noise_base["spread_pct"],
                              noise_ckpt["spread_pct"]),
            "checkpoint_bytes": nbytes,
            "items": len(items),
            "coverage_ok": set(items) == set(flat),
            "restored_step": restored_step,
            "restore_ms": round(restore_s * 1e3, 2),
            "save_ms": {
                "probe_sync": round(save_probe_s * 1e3, 2),
                "mean_total": round(
                    total["sum"] / total["count"] * 1e3, 2)
                if total.get("count") else None,
                "max_total": round((total.get("max") or 0) * 1e3, 2),
                "mean_capture": round(
                    capture["sum"] / capture["count"] * 1e3, 3)
                if capture.get("count") else None,
            },
            # The latency histograms ride the bench artifact next to
            # the rest of the metrics snapshot.
            "metrics": {
                "hvd_ckpt_save_seconds": save_hist,
                "hvd_ckpt_restore_seconds": snap.get(
                    "histograms", {}).get("hvd_ckpt_restore_seconds"),
                "hvd_ckpt_commits_total": snap.get(
                    "counters", {}).get("hvd_ckpt_commits_total"),
                "hvd_ckpt_bytes_total": snap.get(
                    "counters", {}).get("hvd_ckpt_bytes_total"),
            },
        }
    finally:
        mgr.close(timeout=10)
        shutil.rmtree(ckpt_dir, ignore_errors=True)


def check_ckpt_regression(out: dict, repo_dir: str):
    """Same treatment as the smoke headline: warn (stderr + artifact
    field) when the checkpoint cost regressed vs the prior round's
    artifact beyond the run's own noise, when the blocking capture
    path stops being negligible, or when the amortized overhead at
    the derived cadence misses the 5 % target."""
    import glob
    import re
    cur = out.get("checkpoint_smoke") or {}
    if not cur or "error" in cur:
        return
    amortized = cur.get("amortized_overhead_pct")
    if amortized is not None and \
            amortized > cur.get("overhead_target_pct", 5.0):
        print("WARNING: async-checkpoint amortized overhead %.1f%% "
              "exceeds the 5%% target on the CPU smoke trainer"
              % amortized, file=sys.stderr)
    capture = cur.get("capture_overhead_pct")
    if capture is not None and capture > 1.0:
        print("WARNING: checkpoint capture (the training-blocking "
              "phase) cost %.2f%% of the run — the async decoupling "
              "is broken" % capture, file=sys.stderr)
    cur_cost = cur.get("save_cost_ms")
    if cur_cost is None:
        return
    prior = None
    for path in reversed(sorted(glob.glob(
            os.path.join(repo_dir, "BENCH_r*.json")))):
        try:
            with open(path) as f:
                raw = f.read()
        except OSError:
            continue
        m = re.search(
            r'\\?"checkpoint_smoke\\?":\s*\{.*?"save_cost_ms'
            r'":\s*(-?[0-9.]+)', raw, re.S)
        if m and float(m.group(1)) > 0:
            prior = {"save_cost_ms": float(m.group(1)),
                     "source": os.path.basename(path)}
            break
    if prior is None:
        return
    tol_pct = max(float(cur.get("spread_pct") or 0.0), 10.0)
    delta_pct = (cur_cost - prior["save_cost_ms"]) \
        / prior["save_cost_ms"] * 100.0
    cur["ckpt_vs_prior"] = {
        "prior_save_cost_ms": prior["save_cost_ms"],
        "prior_source": prior["source"],
        "delta_pct": round(delta_pct, 1),
        "tolerance_pct": round(tol_pct, 1),
        "regressed": delta_pct > tol_pct,
    }
    if cur["ckpt_vs_prior"]["regressed"]:
        print("WARNING: per-checkpoint cost regressed %.1f%% vs %s "
              "(%.0f ms -> %.0f ms per save), beyond the %.1f%% "
              "noise band"
              % (delta_pct, prior["source"],
                 prior["save_cost_ms"], cur_cost, tol_pct),
              file=sys.stderr)


# ---------------------------------------------------------------------------
# Recovery lane: measured MTTR (detect -> restore -> resume)
# ---------------------------------------------------------------------------

def bench_recovery(args, smoke: bool) -> dict:
    """MTTR with a number on it: the chaos MTTR drill (8 in-process
    ranks over the real control plane, liveness + reconnect armed,
    durable checkpoints) killed/wedged/transiently-dropped repeatedly;
    the artifact records kill-to-first-post-restore-step percentiles,
    the detection bound actually achieved, and whether the replay fast
    path re-engaged after every recovery — the recovery analog of the
    tiny-op floor."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    from chaos_soak import _percentile, run_mttr_drill

    reps = 2 if smoke else 4
    interval = 0.4
    cells = []
    for rep in range(reps):
        for fault in ("kill", "wedge"):
            cells.append(run_mttr_drill(
                fault=fault, when="idle", ranks=8, seed=rep,
                liveness_interval_s=interval))
    drop = run_mttr_drill(fault="conn_drop", when="during_negotiation",
                          ranks=8, seed=0,
                          liveness_interval_s=interval)
    mttrs = [c["mttr_s"] for c in cells if c.get("mttr_s") is not None]
    detects = {fault: [c["detect_s"] for c in cells
                       if c["fault"] == fault and
                       c.get("detect_s") is not None]
               for fault in ("kill", "wedge")}
    restores = [c["restore_s"] for c in cells
                if c.get("restore_s") is not None]
    # Flight-recorder postmortems (tools/blackbox_merge.py): every
    # kill/wedge cell now carries a causally merged detect→promote→
    # restore→resume breakdown derived from the per-rank event dumps —
    # the artifact embeds the per-phase medians instead of only the
    # coarse wall-clock timers above.
    pm_spans = [c["postmortem"]["spans"] for c in cells
                if (c.get("postmortem") or {}).get("spans")]
    breakdown_ms = {
        phase: round(1e3 * _percentile(
            [s[phase] for s in pm_spans if phase in s], 50), 1)
        for phase in ("detect", "promote", "restore", "resume",
                      "total")
    } if pm_spans else None
    from horovod_tpu.common import metrics as _hm
    snap = _hm.snapshot()
    return {
        "ranks": 8,
        "liveness_interval_s": interval,
        "cells": len(cells) + 1,
        "cells_ok": all(c.get("ok") for c in cells) and drop.get("ok"),
        "postmortem_breakdown_ms": breakdown_ms,
        "postmortem_named_victim_all": all(
            (c.get("postmortem") or {}).get("named_victim")
            for c in cells),
        "mttr_ms": {
            "p50": round(1e3 * _percentile(mttrs, 50), 1)
            if mttrs else None,
            "p90": round(1e3 * _percentile(mttrs, 90), 1)
            if mttrs else None,
            "max": round(1e3 * max(mttrs), 1) if mttrs else None,
        },
        # Wedge detection is bounded by the heartbeat machinery
        # (~2x interval + sweep); kill detection additionally waits
        # out the reconnect grace window (a closed socket might be a
        # transient drop) — two different protocol bounds.
        "detect_ms": {
            "wedge_p50": round(1e3 * _percentile(detects["wedge"], 50),
                               1) if detects["wedge"] else None,
            "wedge_max": round(1e3 * max(detects["wedge"]), 1)
            if detects["wedge"] else None,
            "wedge_bound_ms": round(1e3 * 2 * interval, 1),
            "kill_p50": round(1e3 * _percentile(detects["kill"], 50),
                              1) if detects["kill"] else None,
            "kill_max": round(1e3 * max(detects["kill"]), 1)
            if detects["kill"] else None,
            # grace window + EOF-notice poll + expiry sweep
            "kill_bound_ms": round(1e3 * (2 * interval + interval), 1),
        },
        "restore_ms_p50": round(1e3 * _percentile(restores, 50), 2)
        if restores else None,
        "replay_reengaged_all": all(c.get("replay_reengaged")
                                    for c in cells),
        "transient_drop": {
            "ok": drop.get("ok"),
            "reconnects_resumed": drop.get("reconnects_resumed"),
            "fatal_events": drop.get("fatal_events"),
        },
        "metrics": {
            "hvd_recovery_seconds": snap.get("histograms", {}).get(
                "hvd_recovery_seconds"),
            "hvd_reconnects_total": snap.get("counters", {}).get(
                "hvd_reconnects_total"),
            "hvd_liveness_timeouts_total": snap.get(
                "counters", {}).get("hvd_liveness_timeouts_total"),
        },
    }


def bench_blackbox(args, smoke: bool) -> dict:
    """Flight-recorder cost, measured: the disabled hot-path guard
    (ONE module-attribute check — the number the perf-pin test bounds)
    and the enabled per-event record cost (tuple build + bounded
    deque.append), plus a dump+merge wall time for a full ring so the
    postmortem path itself has a tracked number."""
    import shutil
    import tempfile
    import timeit

    from horovod_tpu.common import flight_recorder as fr

    fr.reset()
    n = 200_000
    # The exact site shape: short-circuit on the module attribute, so
    # record() is never entered while disabled.
    disabled_ns = timeit.timeit(
        "fr.ENABLED and fr.record(fr.SUBMIT, name='bench.t')",
        globals={"fr": fr}, number=n) / n * 1e9
    fr.configure(capacity=8192, enabled=True)
    enabled_ns = timeit.timeit(
        "fr.record(fr.SUBMIT, rank=0, name='bench.t', type='ALLREDUCE')",
        globals={"fr": fr}, number=n) / n * 1e9
    # Dump + merge a full ring: the cost of actually using the black
    # box after a failure (never on the hot path).
    bb_dir = tempfile.mkdtemp(prefix="hvd-bb-bench-")
    t0 = time.perf_counter()
    try:
        fr.record(fr.FRAME_TX, rank=1, role="worker", frame="HB",
                  nbytes=0)
        paths = fr.dump("bench", directory=bb_dir)
        dump_ms = (time.perf_counter() - t0) * 1e3
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "tools"))
        import blackbox_merge
        t1 = time.perf_counter()
        trace, _verdict = blackbox_merge.merge(bb_dir)
        merge_ms = (time.perf_counter() - t1) * 1e3
    finally:
        fr.reset()
        shutil.rmtree(bb_dir, ignore_errors=True)
    return {
        "disabled_ns_per_check": round(disabled_ns, 1),
        "enabled_ns_per_event": round(enabled_ns, 1),
        "ring_capacity": 8192,
        "dumps": len(paths),
        "dump_ms": round(dump_ms, 2),
        "merge_full_ring_ms": round(merge_ms, 2),
        "merged_trace_events": len(trace),
    }


def _prior_bench_value(repo_dir: str, pattern: str):
    """Newest prior BENCH_r*.json whose raw text matches ``pattern``
    (group 1 = a positive number): the shared scan every *_vs_prior
    regression check performs.  Returns (value, basename) or None."""
    import glob
    import re
    for path in reversed(sorted(glob.glob(
            os.path.join(repo_dir, "BENCH_r*.json")))):
        try:
            with open(path) as f:
                raw = f.read()
        except OSError:
            continue
        m = re.search(pattern, raw, re.S)
        if m and float(m.group(1)) > 0:
            return float(m.group(1)), os.path.basename(path)
    return None


def check_blackbox_regression(out: dict, repo_dir: str):
    """The recorder's costs are regression-warned like the smoke
    headline: the disabled guard must stay in attribute-check
    territory, and the enabled per-event cost must not grow past the
    noise band vs the prior round's artifact."""
    cur = out.get("blackbox") or {}
    if not cur or "error" in cur:
        return
    if cur.get("disabled_ns_per_check", 0) > 1000:
        print("WARNING: flight-recorder disabled guard costs %.0f ns "
              "(>1us): no longer a bare attribute check"
              % cur["disabled_ns_per_check"], file=sys.stderr)
    prior = _prior_bench_value(
        repo_dir, r'"blackbox":\s*\{[^}]*?"enabled_ns_per_event":\s*'
                  r'(-?[0-9.]+)')
    if prior is None:
        return  # first round with a blackbox lane
    prior_ns, prior_source = prior
    tol_pct = 100.0  # ns-scale timeit on a shared CPU: wide band
    delta_pct = (cur["enabled_ns_per_event"] - prior_ns) \
        / prior_ns * 100.0
    cur["blackbox_vs_prior"] = {
        "prior_enabled_ns": prior_ns,
        "prior_source": prior_source,
        "delta_pct": round(delta_pct, 1),
        "tolerance_pct": tol_pct,
        "regressed": delta_pct > tol_pct,
    }
    if cur["blackbox_vs_prior"]["regressed"]:
        print("WARNING: flight-recorder enabled cost regressed "
              "%.1f%% vs %s (%.0f ns -> %.0f ns)"
              % (delta_pct, prior_source, prior_ns,
                 cur["enabled_ns_per_event"]), file=sys.stderr)


def check_recovery_regression(out: dict, repo_dir: str):
    """MTTR is a regression-gated bench number like the smoke
    headline: warn (stderr + artifact field) when the p50 MTTR grew
    beyond the noise band vs the prior round's artifact, or when any
    drill cell failed outright."""
    import glob
    import re
    cur = out.get("recovery") or {}
    if not cur or "error" in cur:
        return
    if not cur.get("cells_ok"):
        print("WARNING: recovery drill cells failed — the self-healing "
              "control plane is broken, not just slow", file=sys.stderr)
    cur_mttr = (cur.get("mttr_ms") or {}).get("p50")
    if cur_mttr is None:
        return
    prior = None
    for path in reversed(sorted(glob.glob(
            os.path.join(repo_dir, "BENCH_r*.json")))):
        try:
            with open(path) as f:
                raw = f.read()
        except OSError:
            continue
        m = re.search(
            r'\\?"recovery\\?":\s*\{.*?"mttr_ms\\?":\s*\{[^}]*?"p50'
            r'\\?":\s*(-?[0-9.]+)', raw, re.S)
        if m and float(m.group(1)) > 0:
            prior = {"mttr_p50_ms": float(m.group(1)),
                     "source": os.path.basename(path)}
            break
    if prior is None:
        return  # first round with a recovery lane
    tol_pct = 30.0  # wall-clock drill on a shared CPU: wide noise band
    delta_pct = (cur_mttr - prior["mttr_p50_ms"]) \
        / prior["mttr_p50_ms"] * 100.0
    cur["recovery_vs_prior"] = {
        "prior_mttr_p50_ms": prior["mttr_p50_ms"],
        "prior_source": prior["source"],
        "delta_pct": round(delta_pct, 1),
        "tolerance_pct": tol_pct,
        "regressed": delta_pct > tol_pct,
    }
    if cur["recovery_vs_prior"]["regressed"]:
        print("WARNING: p50 MTTR regressed %.1f%% vs %s "
              "(%.0f ms -> %.0f ms), beyond the %.0f%% noise band"
              % (delta_pct, prior["source"], prior["mttr_p50_ms"],
                 cur_mttr, tol_pct), file=sys.stderr)


def bench_autoscale(args, smoke: bool) -> dict:
    """Autoscale latency with a number on it: the closed-loop
    elasticity drill (policy scale-up -> checkpoint-first straggler
    migration -> shrink, tools/chaos_soak.run_autoscale_drill)
    repeated with the synthetic signal source; the artifact records
    the decision -> admitted -> first-post-resize-step breakdown and
    its p50 headline — the elasticity analog of the MTTR lane."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    from chaos_soak import _percentile, run_autoscale_drill

    reps = 2 if smoke else 4
    cells = []
    for rep in range(reps):
        cells.append(run_autoscale_drill(
            ranks=8, grow_to=16, seed=rep, policy_window=3,
            policy_cooldown_s=1.0, migrate_after_s=0.2))

    def lane(key, phase):
        vals = [(c.get(key) or {}).get(phase) for c in cells]
        vals = [v for v in vals if v is not None]
        return {"p50_ms": round(1e3 * _percentile(vals, 50), 1)
                if vals else None,
                "max_ms": round(1e3 * max(vals), 1) if vals else None}

    from horovod_tpu.common import metrics as _hm
    snap = _hm.snapshot()
    return {
        "ranks": 8, "grow_to": 16, "cells": len(cells),
        "cells_ok": all(c.get("ok") for c in cells),
        # The headline: scale-up decision -> first post-resize step.
        "autoscale_ms": lane("scale_up_s", "first_step"),
        "scale_up_ms": {phase: lane("scale_up_s", phase)
                        for phase in ("decision", "admission",
                                      "first_step")},
        "migrate_ms": {phase: lane("migrate_s", phase)
                       for phase in ("decision", "ckpt_wait",
                                     "first_step")},
        "step_loss_max": max(
            [max(c.get("step_loss_a", 0), c.get("step_loss_b", 0))
             for c in cells] or [None]),
        "postmortem_named_triggers_all": all(
            (c.get("postmortem") or {}).get("named_resize_triggers")
            for c in cells),
        "metrics": {
            "hvd_autoscale_seconds": snap.get("histograms", {}).get(
                "hvd_autoscale_seconds"),
            "hvd_elastic_resizes_total": snap.get(
                "counters", {}).get("hvd_elastic_resizes_total"),
        },
    }


def check_autoscale_regression(out: dict, repo_dir: str):
    """The autoscale headline (scale-up decision -> first post-resize
    step p50) is regression-warned against the prior round's artifact,
    same contract as the MTTR lane."""
    cur = out.get("autoscale") or {}
    if not cur or "error" in cur:
        return
    if not cur.get("cells_ok"):
        print("WARNING: autoscale drill cells failed — the closed "
              "elasticity loop is broken, not just slow",
              file=sys.stderr)
    cur_p50 = (cur.get("autoscale_ms") or {}).get("p50_ms")
    if cur_p50 is None:
        return
    prior = _prior_bench_value(
        repo_dir, r'"autoscale\\?":\s*\{.*?"autoscale_ms\\?":\s*'
                  r'\{[^}]*?"p50_ms\\?":\s*(-?[0-9.]+)')
    if prior is None:
        return  # first round with an autoscale lane
    prior_ms, prior_source = prior
    tol_pct = 30.0  # wall-clock drill on a shared CPU: wide noise band
    delta_pct = (cur_p50 - prior_ms) / prior_ms * 100.0
    cur["autoscale_vs_prior"] = {
        "prior_p50_ms": prior_ms,
        "prior_source": prior_source,
        "delta_pct": round(delta_pct, 1),
        "tolerance_pct": tol_pct,
        "regressed": delta_pct > tol_pct,
    }
    if cur["autoscale_vs_prior"]["regressed"]:
        print("WARNING: p50 autoscale latency regressed %.1f%% vs %s "
              "(%.0f ms -> %.0f ms), beyond the %.0f%% noise band"
              % (delta_pct, prior_source, prior_ms, cur_p50, tol_pct),
              file=sys.stderr)


# ---------------------------------------------------------------------------
# Eager allreduce micro-benchmark (2 real processes, real control plane)
# ---------------------------------------------------------------------------

_WORKER_SRC = r"""
import json, os, sys, time
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import horovod_tpu as hvd

hvd.init()
RANK = hvd.rank()
sizes_mb = json.loads(os.environ["BENCH_SIZES_MB"])
ITERS_CAP = int(os.environ.get("BENCH_ITERS_CAP", "0"))
results = []
for mb in sizes_mb:
    n = int(mb * 1024 * 1024 // 4)
    iters = max(3, int(64 / mb))
    if ITERS_CAP:
        # Scale lanes (8-16 ranks on a shared CPU) cap the per-size op
        # count so the lane measures scaling, not the rig's patience.
        iters = min(iters, ITERS_CAP)
    for kind in ("numpy", "jax"):
        buf = np.full((n,), float(RANK + 1), np.float32)
        if kind == "jax":
            buf = jax.numpy.asarray(buf)
        name = "bench.%s.%s" % (mb, kind)
        # Warmup: negotiation + compile, growing the persistent fusion
        # staging buffer and faulting in fresh output pages; 3 rounds
        # so the first timed iteration of each size/kind measures the
        # steady state, not allocator churn (measured: the first lane
        # at a new size otherwise reads ~30% low).
        for _ in range(3):
            out = hvd.allreduce(buf, op=hvd.Sum, name=name)
        np.asarray(out)
        # Chunked timing: on the 1-core rig the driver benches on,
        # scheduler jitter swings a single long loop by ~±15%; per-
        # chunk throughputs expose that spread in the artifact (median
        # = honest expectation, best = the floor the design reaches
        # when not preempted).
        chunks = []
        per = max(iters // 5, 1)
        for _ in range(5):  # odd count: chunks[2] is a true median
            t0 = time.perf_counter()
            for _ in range(per):
                out = hvd.allreduce(buf, op=hvd.Sum, name=name)
            np.asarray(out)
            chunks.append(mb / 1024 * per /
                          (time.perf_counter() - t0))
        chunks.sort()
        results.append({
            "size_mb": mb, "input": kind, "iters": 5 * per,
            "gbps": round(chunks[2], 3),
            "gbps_best": round(chunks[-1], 3),
            "gbps_spread": [round(chunks[0], 3), round(chunks[-1], 3)],
        })


def timed_floor(fn, warmup=5, chunks=5, per=40):
    for _ in range(warmup):
        fn()
    ms = []
    for _ in range(chunks):
        t0 = time.perf_counter()
        for _ in range(per):
            fn()
        ms.append((time.perf_counter() - t0) / per * 1e3)
    ms.sort()
    return {"median_ms": round(ms[len(ms) // 2], 3),
            "best_ms": round(ms[0], 3),
            "worst_ms": round(ms[-1], 3)}


# Control-plane latency floor: a 1-element allreduce and a barrier
# time the pure submit->CH->CB->dispatch->callback round (no data).
# Two lanes: replay DISABLED measures the negotiated CH/CB round-trip
# (the pre-round-6 steady state); replay ENABLED measures the frozen-
# schedule fast path, with the uplink frame counters sampled around it
# to prove the replayed ops put ZERO frames on the wire.
from horovod_tpu.common import basics
from horovod_tpu.common import metrics as _hm
_rt = basics._state().runtime
_rp = _rt.replay

tiny = np.ones(1, np.float32)


def tiny_op():
    hvd.allreduce(tiny, op=hvd.Sum, name="bench.tiny")


if _rp is not None:
    _rp.set_enabled(False)
tiny_floor = timed_floor(tiny_op)
barrier_floor = timed_floor(hvd.barrier)

replay_floor = None
replay_engaged = False
frames_during_replay = None
if _rp is not None:
    _rp.set_enabled(True)
    for _ in range(8):   # converge + enter (warmup K cycles)
        tiny_op()
    replay_engaged = bool(_rp.stats()["active"])
    _f0 = dict(_rt.controller.stats)
    replay_floor = timed_floor(tiny_op)
    _f1 = dict(_rt.controller.stats)
    frames_during_replay = sum(
        _f1[k] - _f0[k] for k in ("rq_frames", "ch_frames"))

_c = _hm.REGISTRY.counter
replay_stats = {
    "engaged": replay_engaged,
    "entries": _c("hvd_steady_state_entries").value(),
    "cycles_replayed":
        _c("hvd_steady_state_cycles_replayed").value(),
    "exits": _c("hvd_steady_state_exits").snapshot() or {},
    "uplink_frames_during_replay_floor": frames_during_replay,
}

stats = dict(basics._state().runtime.controller.stats)
backend_stats = dict(getattr(basics._state().backend, "stats", {}))
# Registry snapshot: records fusion efficiency, cache hit rate, and
# the cycle/submit latency histograms in the BENCH artifact, so the
# perf trajectory carries structure, not just wall time.
metrics_snap = hvd.metrics_snapshot()
if RANK == 0:
    print("BENCHJSON " + json.dumps({
        "results": results, "frames": stats,
        "metrics": metrics_snap,
        "replay": replay_stats,
        "tune": hvd.tune_status(),
        "backend": {"type": type(basics._state().backend).__name__,
                    "ring_shm": backend_stats.get("ring_shm"),
                    "ring_allreduces":
                        backend_stats.get("ring_allreduces")},
        "control_floor": {
            "tiny_allreduce_ms": tiny_floor["median_ms"],
            "tiny_allreduce": tiny_floor,
            "tiny_replay_ms": (replay_floor or {}).get("median_ms"),
            "tiny_replay": replay_floor,
            "barrier_ms": barrier_floor["median_ms"],
            "barrier": barrier_floor}}))
hvd.shutdown()
"""


_DLRM_WORKER_SRC = r"""
import json, os, sys, time
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import horovod_tpu as hvd
from horovod_tpu.common import metrics as _hm
from horovod_tpu.models import (DLRMDense, bce_logits_loss,
                                dlrm_tiny_config,
                                synthetic_click_batch)
from horovod_tpu.sparse import EmbeddingBag, ShardedEmbedding

hvd.init()
RANK, SIZE = hvd.rank(), hvd.size()
BATCH = int(os.environ.get("BENCH_DLRM_BATCH", "32"))
STEPS = int(os.environ.get("BENCH_DLRM_STEPS", "10"))
CADENCE = int(os.environ.get("BENCH_DLRM_CKPT_EVERY", "5"))
LR = 0.05

cfg = dlrm_tiny_config()
tables = [ShardedEmbedding("dlrm.t%d" % i, rows, cfg.embed_dim,
                           seed=7 + i)
          for i, rows in enumerate(cfg.table_rows)]
bags = [EmbeddingBag(t, mode="mean") for t in tables]

model = DLRMDense(cfg)
rng0 = jax.random.PRNGKey(0)
dense0 = np.zeros((BATCH, cfg.num_dense), np.float32)
emb0 = np.zeros((BATCH, cfg.num_tables * cfg.embed_dim), np.float32)
params = jax.jit(lambda r, d, e: model.init(r, d, e))(
    rng0, dense0, emb0)


def loss_fn(params, dense_x, emb_in, labels):
    return bce_logits_loss(model.apply(params, dense_x, emb_in),
                           labels)


grad_fn = jax.jit(jax.value_and_grad(loss_fn, argnums=(0, 2)))
flat_tmpl = None


def one_step(step_idx):
    # Per-rank, per-step batch: splits legitimately vary every step —
    # the traffic pattern steady-state replay must never freeze.
    global params, flat_tmpl
    rng = np.random.default_rng([RANK, step_idx])
    dense_x, ids, offsets, labels = synthetic_click_batch(
        rng, BATCH, cfg)
    embs = [bag.forward(ids[i], offsets)
            for i, bag in enumerate(bags)]        # alltoall x2/table
    emb_in = np.concatenate(embs, axis=1)
    loss, (gparams, gemb) = grad_fn(params, dense_x, emb_in, labels)
    flat, tree = jax.flatten_util.ravel_pytree(gparams)
    flat = np.asarray(flat)
    flat = np.asarray(hvd.allreduce(flat, op=hvd.Average,
                                    name="dlrm.densegrad"))
    gparams = tree(jax.numpy.asarray(flat))
    params = jax.tree_util.tree_map(lambda p, g: p - LR * g,
                                    params, gparams)
    gemb = np.asarray(gemb)
    for i, bag in enumerate(bags):               # alltoall x1/table
        bag.backward(gemb[:, i * cfg.embed_dim:
                          (i + 1) * cfg.embed_dim], lr=LR)
    return float(loss)


import jax.flatten_util  # noqa: E402  (after jax config)

# Warmup: negotiation + jit compile.
for s in range(3):
    one_step(s)

def _a2a_bytes():
    c = (_hm.snapshot()["counters"]
         .get("hvd_sparse_alltoall_bytes_total") or {})
    return sum(c.values()) if isinstance(c, dict) else float(c)

chunks, losses = [], []
per = max(STEPS // 3, 1)
sidx = 3
for _ in range(3):
    b0 = _a2a_bytes()
    t0 = time.perf_counter()
    for _ in range(per):
        losses.append(one_step(sidx))
        sidx += 1
    dt = time.perf_counter() - t0
    chunks.append({"steps_per_sec": per / dt,
                   "alltoall_gbps": (_a2a_bytes() - b0) / dt / 2**30})
chunks.sort(key=lambda c: c["steps_per_sec"])
mid = chunks[len(chunks) // 2]

# --- differential checkpoint cost under the REAL multi-rank commit
# protocol (ROADMAP 3c): every worker rank writes its own shard and
# marks prepare through the rendezvous-KV commit coordinator; rank 0
# arbitrates the marks and publishes the manifest — no single-rank
# stand-in.  Bytes come from the committed manifests themselves (the
# sum of every rank's shard nbytes), so the ratio covers the whole
# world's shards.
ckpt = None
mgr = coord = None
KV = os.environ.get("BENCH_DLRM_KV")
CDIR = os.environ.get("BENCH_DLRM_CKPT_DIR")
if KV and CDIR:
    from horovod_tpu.checkpoint import (CheckpointManager,
                                        KVCommitCoordinator, RowDelta,
                                        read_manifest, step_dir)
    from horovod_tpu.runner.http_server import RendezvousClient
    host, port = KV.rsplit(":", 1)
    coord = KVCommitCoordinator(RendezvousClient(host, int(port),
                                                 timeout=30.0))
    mgr = CheckpointManager(CDIR, rank=RANK, world_size=SIZE,
                            coordinator=coord, keep=4)

    def _wait_committed(step, deadline=120.0):
        # save() returns at "prepared" on non-arbiter ranks; the next
        # delta_plan() must see the committed manifest, so every rank
        # waits for the arbiter's publish before moving on.
        t0 = time.perf_counter()
        while (coord.committed_step() or -1) < step:
            if time.perf_counter() - t0 > deadline:
                raise RuntimeError("step %d commit not visible" % step)
            time.sleep(0.02)

    def _step_bytes(step):
        man = read_manifest(step_dir(CDIR, step))
        return sum(int(e.get("nbytes", 0)) for e in man.shards)

    dense_np = {"dense/p%d" % i: np.asarray(l) for i, l in
                enumerate(jax.tree_util.tree_leaves(params))}
    local = {}
    for t in tables:
        local.update(t.durable_items(full=True))
        t.clear_touched()
    t0 = time.perf_counter()
    mgr.save(1, dense_np, local_items=local)
    full_ms = (time.perf_counter() - t0) * 1e3
    _wait_committed(1)
    full_bytes = _step_bytes(1)
# CADENCE more steps on every rank (collective), then the delta.
for _ in range(CADENCE):
    losses.append(one_step(sidx))
    sidx += 1
if mgr is not None:
    touched = sum(t.touched_count() for t in tables)
    local = {}
    for t in tables:
        local.update(t.durable_items(full=False))
    plan = mgr.delta_plan()
    t0 = time.perf_counter()
    mgr.save(2, dense_np, local_items=local, delta_of=plan)
    delta_ms = (time.perf_counter() - t0) * 1e3
    _wait_committed(2)
    delta_bytes = _step_bytes(2)
    # Round-trip check on EVERY rank: base+delta must replay to
    # exactly this rank's live shard.
    step, items = mgr.restore_latest()
    ok = all(
        items[t.item_name()] == RowDelta(t.local_ids, t.local,
                                         t.num_rows)
        for t in tables)
    assert ok, "rank %d: delta roundtrip mismatch" % RANK
    mgr.close()
    if RANK == 0:
        ckpt = {
            "full_save_ms": round(full_ms, 2),
            "delta_save_ms": round(delta_ms, 2),
            "full_bytes": full_bytes,
            "delta_bytes": delta_bytes,
            "delta_vs_full_bytes_ratio":
                round(delta_bytes / full_bytes, 4),
            "touched_rows": touched,
            "table_rows_per_rank":
                sum(len(t.local_ids) for t in tables),
            "cadence_steps": CADENCE,
            "delta_of": plan,
            "world_size_commits": SIZE,
            "coordinator": "kv",
            "roundtrip_bit_identical": bool(ok),
        }

snap = hvd.metrics_snapshot()
if RANK == 0:
    counters = snap.get("counters", {})
    print("BENCHJSON " + json.dumps({
        "nproc": SIZE, "batch_per_rank": BATCH,
        "tables": [{"rows": r, "dim": cfg.embed_dim}
                   for r in cfg.table_rows],
        "steps_per_sec": round(mid["steps_per_sec"], 3),
        "steps_per_sec_spread": [
            round(chunks[0]["steps_per_sec"], 3),
            round(chunks[-1]["steps_per_sec"], 3)],
        "alltoall_gbps": round(mid["alltoall_gbps"], 4),
        "loss_first": round(losses[0], 4),
        "loss_last": round(losses[-1], 4),
        "checkpoint": ckpt,
        "sparse_alltoall": {
            "ops": counters.get("hvd_sparse_alltoall_ops_total"),
            "bytes": counters.get("hvd_sparse_alltoall_bytes_total")},
        "steady_state_exits":
            counters.get("hvd_steady_state_exits"),
        "metrics": snap,
    }))
hvd.shutdown()
"""


# Serving-plane trainer worker (docs/serving.md): the DLRM-tiny loop
# with PERIODIC multi-rank KV commits — every CADENCE steps the world
# persists a differential checkpoint through the real commit protocol,
# feeding the manifest stream the parent's ServingReplica tails while
# this loop keeps training.  The parent drives Zipf queries against
# the replica concurrently; this worker only reports the commit
# timeline (step + wall time per commit) so freshness lag can be
# attributed against the trainer's own clock.
_SERVE_TRAINER_SRC = r"""
import json, os, sys, time
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import horovod_tpu as hvd
from horovod_tpu.models import (DLRMDense, bce_logits_loss,
                                dlrm_tiny_config,
                                synthetic_click_batch)
from horovod_tpu.sparse import EmbeddingBag, ShardedEmbedding
from horovod_tpu.checkpoint import (CheckpointManager,
                                    KVCommitCoordinator)
from horovod_tpu.runner.http_server import RendezvousClient

hvd.init()
RANK, SIZE = hvd.rank(), hvd.size()
BATCH = int(os.environ.get("BENCH_SERVE_BATCH", "32"))
STEPS = int(os.environ.get("BENCH_SERVE_TRAIN_STEPS", "30"))
CADENCE = int(os.environ.get("BENCH_SERVE_CKPT_EVERY", "3"))
LR = 0.05

cfg = dlrm_tiny_config()
tables = [ShardedEmbedding("dlrm.t%d" % i, rows, cfg.embed_dim,
                           seed=7 + i)
          for i, rows in enumerate(cfg.table_rows)]
bags = [EmbeddingBag(t, mode="mean") for t in tables]

model = DLRMDense(cfg)
rng0 = jax.random.PRNGKey(0)
dense0 = np.zeros((BATCH, cfg.num_dense), np.float32)
emb0 = np.zeros((BATCH, cfg.num_tables * cfg.embed_dim), np.float32)
params = jax.jit(lambda r, d, e: model.init(r, d, e))(
    rng0, dense0, emb0)


def loss_fn(params, dense_x, emb_in, labels):
    return bce_logits_loss(model.apply(params, dense_x, emb_in),
                           labels)


grad_fn = jax.jit(jax.value_and_grad(loss_fn, argnums=(0, 2)))


def one_step(step_idx):
    global params
    rng = np.random.default_rng([RANK, step_idx])
    dense_x, ids, offsets, labels = synthetic_click_batch(
        rng, BATCH, cfg)
    embs = [bag.forward(ids[i], offsets)
            for i, bag in enumerate(bags)]
    emb_in = np.concatenate(embs, axis=1)
    loss, (gparams, gemb) = grad_fn(params, dense_x, emb_in, labels)
    flat, tree = jax.flatten_util.ravel_pytree(gparams)
    flat = np.asarray(hvd.allreduce(np.asarray(flat), op=hvd.Average,
                                    name="dlrm.densegrad"))
    gparams = tree(jax.numpy.asarray(flat))
    params = jax.tree_util.tree_map(lambda p, g: p - LR * g,
                                    params, gparams)
    gemb = np.asarray(gemb)
    for i, bag in enumerate(bags):
        bag.backward(gemb[:, i * cfg.embed_dim:
                          (i + 1) * cfg.embed_dim], lr=LR)
    return float(loss)


import jax.flatten_util  # noqa: E402

host, port = os.environ["BENCH_SERVE_KV"].rsplit(":", 1)
coord = KVCommitCoordinator(RendezvousClient(host, int(port),
                                             timeout=30.0))
# keep=None: the parent verifies served rows against committed steps
# AFTER the run — GC must not collect them out from under the gate.
mgr = CheckpointManager(os.environ["BENCH_SERVE_CKPT_DIR"], rank=RANK,
                        world_size=SIZE, coordinator=coord, keep=None)


def wait_committed(step, deadline=120.0):
    t0 = time.perf_counter()
    while (coord.committed_step() or -1) < step:
        if time.perf_counter() - t0 > deadline:
            raise RuntimeError("step %d commit not visible" % step)
        time.sleep(0.02)


commits, save_ms = [], []
for step in range(1, STEPS + 1):
    one_step(step)
    if step % CADENCE == 0:
        plan = mgr.delta_plan()
        local, snaps = {}, []
        for t in tables:
            snap = t.snapshot_touched()
            local.update(t.durable_items(full=plan is None))
            snaps.append((t, snap))
        dense_np = {"dense/p%d" % i: np.asarray(l) for i, l in
                    enumerate(jax.tree_util.tree_leaves(params))}
        t0 = time.perf_counter()
        mgr.save(step, dense_np, local_items=local, delta_of=plan)
        save_ms.append((time.perf_counter() - t0) * 1e3)
        wait_committed(step)
        for t, snap in snaps:
            t.clear_touched(None if plan is None else snap)
        commits.append({"step": step, "t": round(time.time(), 3),
                        "kind": "base" if plan is None else "delta"})
mgr.close()
if RANK == 0:
    print("BENCHJSON " + json.dumps({
        "nproc": SIZE, "batch_per_rank": BATCH,
        "train_steps": STEPS, "commit_cadence": CADENCE,
        "commits": commits,
        "save_ms_mean": round(sum(save_ms) / max(len(save_ms), 1), 2),
    }))
hvd.shutdown()
"""


# Tuned-vs-default lane worker (autotune-then-freeze, docs/autotune.md):
# phase 1 drives a fixed tiny+bulk allreduce mix until the tuning
# session FREEZES (tuned lane) or an equivalent warm-round budget
# elapses (default lane), so both lanes measure after comparable warm
# history; phase 2 measures the steady-state replay floor and bulk
# GB/s under whichever knobs are live, sampling the uplink counters to
# prove the replay window is wire-free in both lanes.
_TUNE_WORKER_SRC = r"""
import json, os, time
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import horovod_tpu as hvd
hvd.init()
RANK, SIZE = hvd.rank(), hvd.size()
from horovod_tpu.common import basics
state = basics._state()
rt = state.runtime
rp = rt.replay

payload_mb = float(os.environ.get("BENCH_TUNE_MB", "1"))
buf = np.ones(int(payload_mb * (1 << 20) // 4), np.float32)
tiny = np.ones(1, np.float32)


def one_round():
    hvd.allreduce(tiny, op=hvd.Sum, name="tune.tiny")
    hvd.allreduce(buf, op=hvd.Sum, name="tune.buf")


deadline = time.monotonic() + float(
    os.environ.get("BENCH_TUNE_WARM_S", "90"))
warm_budget = int(os.environ.get("BENCH_TUNE_WARM_ROUNDS", "60"))
warm_rounds = 0
while time.monotonic() < deadline:
    one_round()
    warm_rounds += 1
    st = hvd.tune_status()
    if st is None:
        if warm_rounds >= warm_budget:
            break
    elif st.get("phase") in ("frozen", "aborted"):
        break
status = hvd.tune_status()
frozen = bool(status and status.get("phase") == "frozen")

for _ in range(10):   # let replay converge + engage under final knobs
    one_round()
replay_active = bool(rp is not None and rp.stats()["active"])


def timed_floor(fn, warmup=5, chunks=5, per=40):
    for _ in range(warmup):
        fn()
    ms = []
    for _ in range(chunks):
        t0 = time.perf_counter()
        for _ in range(per):
            fn()
        ms.append((time.perf_counter() - t0) / per * 1e3)
    ms.sort()
    return {"median_ms": round(ms[len(ms) // 2], 3),
            "best_ms": round(ms[0], 3),
            "worst_ms": round(ms[-1], 3)}


f0 = dict(rt.controller.stats)
floor = timed_floor(lambda: hvd.allreduce(tiny, op=hvd.Sum,
                                          name="tune.tiny"))
f1 = dict(rt.controller.stats)
frames_during_floor = sum(
    f1[k] - f0[k] for k in ("rq_frames", "ch_frames"))

reps = int(os.environ.get("BENCH_TUNE_BULK_REPS", "30"))
t0 = time.perf_counter()
for _ in range(reps):
    hvd.allreduce(buf, op=hvd.Sum, name="tune.buf")
dt = time.perf_counter() - t0
gbps = buf.nbytes * reps / dt / 2**30

if RANK == 0:
    print("BENCHJSON " + json.dumps({
        "warm_rounds": warm_rounds,
        "frozen": frozen,
        "replay_active": replay_active,
        "tiny_floor": floor,
        "tiny_floor_ms": floor["median_ms"],
        "uplink_frames_during_floor": frames_during_floor,
        "bulk_mb": payload_mb,
        "bulk_gbps": round(gbps, 4),
        "tune": status,
        "knobs": {
            "fusion_mb": state.knobs.fusion_threshold_bytes / 2**20,
            "cycle_time_ms": state.knobs.cycle_time_ms,
            "coalesce": state.knobs.request_coalescing,
            "replay_warmup": state.knobs.replay_warmup_cycles,
        },
    }))
hvd.shutdown()
"""


def _tune_env(profile_path=None, max_samples=None):
    """The env contract for a tuned bench pass: deterministic grid
    strategy at bench-scale window sizes (the gp strategy is the
    production default; the lane pins grid so artifact deltas are
    reproducible round over round)."""
    env = {
        "HOROVOD_TUNE": "1",
        "HOROVOD_TUNE_STRATEGY": "grid",
        "HOROVOD_TUNE_CYCLES_PER_SAMPLE": "2",
        "HOROVOD_TUNE_WARMUP_WINDOWS": "1",
    }
    if profile_path:
        env["HOROVOD_TUNE_PROFILE"] = profile_path
    if max_samples:
        env["HOROVOD_TUNE_MAX_SAMPLES"] = str(max_samples)
    return env


def _spawn_benchjson_workers(src: str, nproc: int, extra_env=None):
    """Launch ``nproc`` env-contract CPU worker processes running
    ``src`` WITHOUT waiting — the serve lane queries a live replica
    while its trainers run, so spawn and drain are separate steps."""
    repo = os.path.dirname(os.path.abspath(__file__))
    coord_port, ctrl_port = _free_ports(2)
    procs = []
    for rank in range(nproc):
        env = dict(os.environ)
        env.update({
            "HOROVOD_RANK": str(rank), "HOROVOD_SIZE": str(nproc),
            "HOROVOD_LOCAL_RANK": str(rank),
            "HOROVOD_LOCAL_SIZE": str(nproc),
            "HOROVOD_TPU_COORDINATOR": "127.0.0.1:%d" % coord_port,
            "HOROVOD_CONTROLLER_ADDR": "127.0.0.1:%d" % ctrl_port,
            "HOROVOD_CROSS_RANK": "0", "HOROVOD_CROSS_SIZE": "1",
            "HOROVOD_TPU_FORCE_CPU": "1",
            "PYTHONPATH": repo,
        })
        env.update(extra_env or {})
        env.pop("XLA_FLAGS", None)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", src], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
    return procs


def _drain_benchjson_workers(procs, timeout=900) -> dict:
    """Wait for spawned workers and parse rank 0's BENCHJSON line."""
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
        outs.append(out.decode(errors="replace"))
    for rc, out in zip((p.returncode for p in procs), outs):
        if rc != 0:
            return {"error": "worker rc=%s: %s" % (rc, out[-800:])}
    for line in outs[0].splitlines():
        if line.startswith("BENCHJSON "):
            return json.loads(line[len("BENCHJSON "):])
    return {"error": "no result line: %s" % outs[0][-800:]}


def _run_benchjson_workers(src: str, nproc: int, extra_env=None,
                           timeout=900) -> dict:
    """Spawn ``nproc`` env-contract CPU worker processes running
    ``src`` and parse rank 0's BENCHJSON line — the shared scaffolding
    of every multi-process lane (tune, dlrm, serve)."""
    return _drain_benchjson_workers(
        _spawn_benchjson_workers(src, nproc, extra_env=extra_env),
        timeout=timeout)


def _run_tune_workers(nproc: int, extra_env=None, timeout=600):
    return _run_benchjson_workers(_TUNE_WORKER_SRC, nproc,
                                  extra_env=extra_env, timeout=timeout)


def bench_tune(args, smoke: bool) -> dict:
    """The autotune-then-freeze lane: the same tiny+bulk workload
    measured under default knobs and under a tuned warmup→freeze run
    (grid strategy, a real multi-rank world), reporting floor-ms and
    GB/s deltas plus the frozen profile itself.  The acceptance gate a
    tuned run must meet: never regress the default-knob headline
    (check_tune_regression warns when it does)."""
    import tempfile
    nproc = int(os.environ.get("HOROVOD_BENCH_TUNE_RANKS", "4"))
    # Smoke scales down like every other lane: shorter warm budget,
    # smaller bulk section, and a tighter sample cap so the grid
    # force-converges within the budget.
    sizing = {"BENCH_TUNE_WARM_ROUNDS": "30" if smoke else "60",
              "BENCH_TUNE_WARM_S": "60" if smoke else "120",
              "BENCH_TUNE_BULK_REPS": "12" if smoke else "30"}
    out = {"nproc": nproc, "platform": "cpu"}
    default = _run_tune_workers(nproc, extra_env=dict(sizing))
    out["default"] = default
    if "error" in default:
        return out
    prof_dir = tempfile.mkdtemp(prefix="hvd-bench-tune-")
    prof_path = os.path.join(prof_dir, "profile.json")
    tuned = _run_tune_workers(
        nproc, extra_env=dict(
            sizing, **_tune_env(prof_path,
                                max_samples=8 if smoke else None)))
    out["tuned"] = tuned
    if "error" in tuned:
        return out
    try:
        with open(prof_path) as f:
            out["profile"] = json.loads(f.read())
    except (OSError, ValueError):
        out["profile"] = None
    # Reload pass: a restart with the frozen profile must skip the
    # search entirely (zero warm rounds spent searching — the session
    # starts frozen) and still engage replay.
    reload_run = _run_tune_workers(
        nproc, extra_env=dict(sizing, BENCH_TUNE_WARM_ROUNDS="12",
                              **_tune_env(prof_path)))
    out["reloaded"] = reload_run
    d_floor = default.get("tiny_floor_ms")
    t_floor = tuned.get("tiny_floor_ms")
    if d_floor and t_floor:
        out["tuned_vs_default"] = {
            "floor_delta_ms": round(t_floor - d_floor, 3),
            "floor_delta_pct": round(
                (t_floor - d_floor) / d_floor * 100.0, 1),
            "gbps_delta_pct": round(
                (tuned["bulk_gbps"] - default["bulk_gbps"])
                / default["bulk_gbps"] * 100.0, 1)
            if default.get("bulk_gbps") else None,
            "frozen": tuned.get("frozen"),
            "replay_active_both": bool(
                default.get("replay_active")
                and tuned.get("replay_active")),
        }
    return out


def check_tune_regression(out: dict, repo_dir: str):
    """The tuned lane's gates: (1) same-artifact — a tuned run must
    never regress the default-knob headline beyond the floor
    measurement's own spread; (2) artifact-to-artifact — the tuned
    floor must not regress beyond the noise band vs the prior round's
    tune lane (the smoke/recovery-lane precedent)."""
    cur = out.get("tune") or {}
    cmp = cur.get("tuned_vs_default") or {}
    default = cur.get("default") or {}
    tuned = cur.get("tuned") or {}
    if cmp:
        floor = default.get("tiny_floor") or {}
        spread_pct = 10.0
        if floor.get("median_ms"):
            spread_pct = max(
                10.0, (floor.get("worst_ms", 0) -
                       floor.get("best_ms", 0))
                / floor["median_ms"] * 100.0)
        if (cmp.get("floor_delta_pct") or 0) > spread_pct:
            print("WARNING: the TUNED run regressed the default-knob "
                  "tiny-op floor by %.1f%% (%.3f -> %.3f ms), beyond "
                  "the %.1f%% spread band — autotune-then-freeze must "
                  "never lose to the defaults"
                  % (cmp["floor_delta_pct"],
                     default.get("tiny_floor_ms", -1),
                     tuned.get("tiny_floor_ms", -1), spread_pct),
                  file=sys.stderr)
            cmp["regressed_vs_default"] = True
        if cmp.get("gbps_delta_pct") is not None and \
                cmp["gbps_delta_pct"] < -spread_pct:
            print("WARNING: the TUNED run regressed default bulk GB/s "
                  "by %.1f%%, beyond the %.1f%% band"
                  % (-cmp["gbps_delta_pct"], spread_pct),
                  file=sys.stderr)
            cmp["regressed_vs_default"] = True
        if not tuned.get("frozen"):
            print("WARNING: the tune lane never froze (phase %s) — "
                  "the warmup budget is too small or the search "
                  "wedged" % ((tuned.get("tune") or {}).get("phase")),
                  file=sys.stderr)
    prior = _prior_bench_value(
        repo_dir, r'"tune\\?":.*?"tuned\\?":.*?"tiny_floor_ms\\?":\s*'
                  r'(-?[0-9.]+)')
    t_floor = tuned.get("tiny_floor_ms")
    if prior is not None and t_floor:
        prior_v, src = prior
        tol_pct = 30.0  # micro-floor on a shared core
        delta_pct = (t_floor - prior_v) / prior_v * 100.0
        cur["tune_vs_prior"] = {
            "prior_tiny_floor_ms": prior_v, "prior_source": src,
            "delta_pct": round(delta_pct, 1),
            "tolerance_pct": tol_pct,
            "regressed": delta_pct > tol_pct,
        }
        if cur["tune_vs_prior"]["regressed"]:
            print("WARNING: tuned tiny-op floor regressed %.1f%% vs "
                  "%s (%.3f -> %.3f ms), beyond the %.0f%% band"
                  % (delta_pct, src, prior_v, t_floor, tol_pct),
                  file=sys.stderr)


def _free_ports(n):
    import socket
    socks, ports = [], []
    try:
        for _ in range(n):
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            socks.append(s)
            ports.append(s.getsockname()[1])
    finally:
        for s in socks:
            s.close()
    return ports


def bench_collectives(sizes_mb, nproc=2, timeout=600,
                      plane=None, iters_cap=0, extra_env=None) -> dict:
    """Spawn nproc CPU worker processes exercising hvd.allreduce through
    the full eager path: TCP controller + cache fast path + steady-state
    replay + the data plane (default = native ring incl. same-host shm;
    plane="XLA" forces the XLA mesh backend for a control lane). gbps is
    per-rank effective throughput (payload bytes / wall time)."""
    repo = os.path.dirname(os.path.abspath(__file__))
    coord_port, ctrl_port = _free_ports(2)
    procs = []
    for rank in range(nproc):
        env = dict(os.environ)
        env.update({
            "HOROVOD_RANK": str(rank), "HOROVOD_SIZE": str(nproc),
            "HOROVOD_LOCAL_RANK": str(rank),
            "HOROVOD_LOCAL_SIZE": str(nproc),
            "HOROVOD_CROSS_RANK": "0", "HOROVOD_CROSS_SIZE": "1",
            "HOROVOD_TPU_COORDINATOR": "127.0.0.1:%d" % coord_port,
            "HOROVOD_CONTROLLER_ADDR": "127.0.0.1:%d" % ctrl_port,
            "HOROVOD_TPU_FORCE_CPU": "1",
            "BENCH_SIZES_MB": json.dumps(sizes_mb),
            "BENCH_ITERS_CAP": str(iters_cap),
            "PYTHONPATH": repo,
        })
        # Scrub any ambient plane choice: the baseline lane must be
        # the default (native ring) for the ring-vs-XLA comparison in
        # the artifact to mean anything.
        env.pop("HOROVOD_CPU_OPERATIONS", None)
        if plane:
            env["HOROVOD_CPU_OPERATIONS"] = plane
        env.update(extra_env or {})
        env.pop("XLA_FLAGS", None)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _WORKER_SRC], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
        outs.append(out.decode(errors="replace"))
    for rc, out in zip((p.returncode for p in procs), outs):
        if rc != 0:
            return {"error": "worker rc=%s: %s" % (rc, out[-800:])}
    for line in outs[0].splitlines():
        if line.startswith("BENCHJSON "):
            data = json.loads(line[len("BENCHJSON "):])
            data["nproc"] = nproc
            data["platform"] = "cpu"
            return data
    return {"error": "no result line: %s" % outs[0][-800:]}


def bench_scale(args, smoke: bool) -> dict:
    """The 8-rank eager scale lane (16 behind
    HOROVOD_BENCH_SCALE_RANKS): the same real control plane + data
    plane as `allreduce_eager`, but at the first scale a pod
    deployment would hit — reporting GB/s, the negotiated vs replay
    control floor, the response-cache hit rate, and replay engagement
    beyond 2 ranks."""
    nproc = int(os.environ.get("HOROVOD_BENCH_SCALE_RANKS", "8"))
    sizes = [1] if smoke else [1, 4]
    data = bench_collectives(sizes, nproc=nproc, timeout=900,
                             iters_cap=24)
    if "error" in data:
        return data
    counters = (data.get("metrics") or {}).get("counters") or {}
    cache = counters.get("hvd_response_cache_total") or {}
    if not isinstance(cache, dict):
        cache = {}
    hits = float(cache.get("event=hit", 0.0))
    misses = float(cache.get("event=miss", 0.0))
    data["cache_hit_rate"] = round(hits / (hits + misses), 4) \
        if hits + misses else None
    # Tuned-vs-default pass (autotune-then-freeze): the same lane with
    # HOROVOD_TUNE=1 — the search runs during the sized loops (the
    # production warmup shape), the freeze happens before the control-
    # floor section, so the floor deltas compare tuned replay against
    # default replay.
    try:
        import tempfile
        prof = os.path.join(tempfile.mkdtemp(prefix="hvd-scale-tune-"),
                            "profile.json")
        tuned = bench_collectives(
            sizes, nproc=nproc, timeout=900, iters_cap=24,
            extra_env=_tune_env(prof, max_samples=8))
        if "error" not in tuned:
            d_floor = (data.get("control_floor") or {}).get(
                "tiny_replay_ms")
            t_floor = (tuned.get("control_floor") or {}).get(
                "tiny_replay_ms")
            d_gbps = next((r["gbps"] for r in data.get("results", [])
                           if r.get("input") == "numpy"), None)
            t_gbps = next((r["gbps"] for r in tuned.get("results", [])
                           if r.get("input") == "numpy"), None)
            data["tuned_vs_default"] = {
                "tuned_tiny_replay_ms": t_floor,
                "default_tiny_replay_ms": d_floor,
                "floor_delta_ms": round(t_floor - d_floor, 3)
                if (t_floor and d_floor) else None,
                "gbps_delta_pct": round(
                    (t_gbps - d_gbps) / d_gbps * 100.0, 1)
                if (t_gbps and d_gbps) else None,
                "tune": tuned.get("tune"),
            }
        else:
            data["tuned_vs_default"] = {"error": tuned["error"]}
    except Exception as e:
        data["tuned_vs_default"] = {"error": repr(e)[:300]}
    # The full registry snapshot is already in the 2-proc lane when
    # that lane runs; under --only scale this is the only snapshot,
    # so keep it.
    if args.only != "scale":
        data.pop("metrics", None)
    return data


def bench_coord_scale(args, smoke: bool) -> dict:
    """Relay-tree negotiation-latency lane at {8, 64, 256} simulated
    ranks (tools/chaos_soak.run_scale_lane): protocol-only clients
    drive full negotiation rounds through real relays vs the flat
    star.  The artifact records per-size wall latency, the root's
    serialized fan-out cost (the quantity HOROVOD_COORD_FANOUT bounds
    to O(fanout) — and the honest sub-linearity witness on this
    shared-core rig, where in-process relays cannot parallelize), and
    the deterministic root-sends-per-round counts.  Plus one 64-rank
    relay kill-mid-negotiation drill so the robustness claim rides
    the same artifact."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    from chaos_soak import run_relay_drill, run_scale_lane

    sizes = tuple(int(s) for s in os.environ.get(
        "HOROVOD_BENCH_COORD_SIZES", "8,64,256").split(","))
    fanout = int(os.environ.get("HOROVOD_BENCH_COORD_FANOUT", "8"))
    out = run_scale_lane(sizes=sizes, fanout=fanout,
                         rounds=4 if smoke else 8)
    try:
        drill = run_relay_drill(fault="kill", when="negotiation",
                                ranks=64 if not smoke else 16,
                                fanout=fanout, seed=0)
        out["relay_kill_drill"] = {
            k: drill.get(k) for k in
            ("ranks", "fanout", "rehomed", "rehome_s",
             "rehome_bound_s", "ok")}
    except Exception as e:
        out["relay_kill_drill"] = {"error": repr(e)[:300]}
    return out


def check_coord_scale_regression(out: dict, repo_dir: str):
    """The scale lane is regression-gated like the smoke headline:
    warn when latency-vs-ranks growth goes super-linear, when the
    relay drill fails, or when the root fan-out cost regressed beyond
    the noise band vs the prior round's artifact."""
    import glob
    import re
    cur = out.get("coord_scale") or {}
    if not cur or "error" in cur:
        return
    if cur.get("sublinear") is False:
        print("WARNING: coordinator negotiation latency grew "
              "SUPER-linearly with world size (root broadcast growth "
              "%.1fx over %.0fx ranks) — the relay tree is not "
              "bounding rank-0 fan-out"
              % (cur.get("root_broadcast_growth") or -1,
                 cur.get("rank_growth") or -1), file=sys.stderr)
    drill = cur.get("relay_kill_drill") or {}
    if drill and not drill.get("ok"):
        print("WARNING: the 64-rank relay kill drill failed — "
              "interior fan-out loss is not being survived",
              file=sys.stderr)
    prior = None
    for path in reversed(sorted(glob.glob(
            os.path.join(repo_dir, "BENCH_r*.json")))):
        try:
            with open(path) as f:
                raw = f.read()
        except OSError:
            continue
        m = re.search(
            r'"coord_scale\\?":.*?"root_broadcast_growth\\?":\s*'
            r'(-?[0-9.]+)', raw, re.S)
        if m:
            prior = {"root_broadcast_growth": float(m.group(1)),
                     "source": os.path.basename(path)}
            break
    if prior is None:
        return  # first round with a coord-scale lane
    cur_g = cur.get("root_broadcast_growth")
    if cur_g is None:
        return
    tol_pct = 50.0  # wall-clock micro-measurement on a shared core
    delta_pct = (cur_g - prior["root_broadcast_growth"]) \
        / max(prior["root_broadcast_growth"], 1e-9) * 100.0
    cur["coord_scale_vs_prior"] = {
        "prior_root_broadcast_growth":
            prior["root_broadcast_growth"],
        "prior_source": prior["source"],
        "delta_pct": round(delta_pct, 1),
        "tolerance_pct": tol_pct,
        "regressed": delta_pct > tol_pct,
    }
    if cur["coord_scale_vs_prior"]["regressed"]:
        print("WARNING: coordinator scale growth regressed %.1f%% vs "
              "%s (%.1fx -> %.1fx), beyond the %.0f%% band"
              % (delta_pct, prior["source"],
                 prior["root_broadcast_growth"], cur_g, tol_pct),
              file=sys.stderr)


def bench_straggler(args, smoke: bool) -> dict:
    """Time-to-attribution for the live straggler observatory
    (common/straggler.py): an 8-rank in-process world over the real
    control plane, one rank delayed via the failpoint grammar
    (``runtime.submit=delay``), and the lane measures how long the
    scorer takes to NAME the injected rank — in negotiation mode
    (arrival-order lag EWMAs) and with steady-state replay engaged
    (MR-carried phase summaries after the negotiation-era state is
    wiped).  Each cell also drives ``GET /status`` + ``hvdtop --once``
    from the live world, so the whole acceptance path is the measured
    artifact.  The heavier sweep (fanout trees, more reps) stays
    behind the slow test marker — tier-1 wall budget is near the cap."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    from chaos_soak import _percentile, run_straggler_drill

    reps = 2 if smoke else 4
    out = {"ranks": 8, "delay_ms": 25.0, "victim": 3, "cells": {}}
    for mode in ("negotiation", "replay"):
        cells = []
        for rep in range(reps):
            cells.append(run_straggler_drill(
                mode=mode, ranks=8, victim=3, delay_ms=25.0,
                seed=rep, serve_status=(rep == 0)))
        ttas = [c["tta_s"] for c in cells
                if c.get("tta_s") is not None]
        ttrcs = [c["ttrc_s"] for c in cells
                 if c.get("ttrc_s") is not None]
        out["cells"][mode] = {
            "reps": reps,
            "all_named": all(c.get("named") for c in cells),
            "all_ok": all(c.get("ok") for c in cells),
            "tta_p50_s": round(_percentile(ttas, 50), 3)
            if ttas else None,
            "tta_max_s": round(max(ttas), 3) if ttas else None,
            # WHY latency: fault -> profile digest naming the injected
            # delay site (advisory in the drill verdict, measured here).
            "ttrc_p50_s": round(_percentile(ttrcs, 50), 3)
            if ttrcs else None,
            "root_cause_named": all(
                c.get("root_cause_named") for c in cells),
            "victim_score_min": round(min(
                c["victim_score"] for c in cells), 2),
            "hvdtop_rc": cells[0].get("hvdtop_rc"),
        }
        if mode == "replay":
            out["cells"][mode]["cycles_replayed_at_named_min"] = min(
                (c.get("replay") or {}).get(
                    "cycles_replayed_at_named") or 0 for c in cells)
    from horovod_tpu.common import metrics as _hm
    snap = _hm.snapshot()
    out["metrics"] = {
        "hvd_ready_spread_seconds": snap.get("histograms", {}).get(
            "hvd_ready_spread_seconds"),
        "hvd_critical_path_total": snap.get("counters", {}).get(
            "hvd_critical_path_total"),
        "hvd_straggler_flags_total": snap.get("counters", {}).get(
            "hvd_straggler_flags_total"),
    }
    return out


def check_straggler_regression(out: dict, repo_dir: str):
    """Prior-artifact regression warning on time-to-attribution: a
    big TTA regression means the observatory lost its 'right now'
    property even though the scorer still names the rank."""
    cur = out.get("straggler") or {}
    cells = cur.get("cells") or {}
    for mode, cell in cells.items():
        if not cell.get("all_named"):
            print("WARNING: straggler lane (%s mode) failed to name "
                  "the injected rank" % mode, file=sys.stderr)
    # The capture stays INSIDE the negotiation cell's braces: a prior
    # round whose negotiation cell failed writes tta_p50_s: null, and
    # a sliding .*? match would then grab the replay cell's number —
    # comparing across modes.
    prior = _prior_bench_value(
        repo_dir,
        r'"straggler\\?":.*?"negotiation\\?":\s*\{[^{}]*?'
        r'"tta_p50_s\\?":\s*([0-9.]+)')
    if prior is None:
        return  # first round with a (named) straggler lane
    cur_tta = (cells.get("negotiation") or {}).get("tta_p50_s")
    if cur_tta is None:
        return
    prior_tta, prior_source = prior
    tol_pct = 100.0  # sub-second measurement on a shared core
    delta_pct = (cur_tta - prior_tta) / max(prior_tta, 1e-9) * 100.0
    cur["straggler_vs_prior"] = {
        "prior_tta_p50_s": prior_tta,
        "prior_source": prior_source,
        "delta_pct": round(delta_pct, 1),
        "tolerance_pct": tol_pct,
        "regressed": delta_pct > tol_pct,
    }
    if cur["straggler_vs_prior"]["regressed"]:
        print("WARNING: straggler time-to-attribution regressed "
              "%.1f%% vs %s (%.3fs -> %.3fs), beyond the %.0f%% band"
              % (delta_pct, prior_source, prior_tta,
                 cur_tta, tol_pct), file=sys.stderr)
    # Same contract for time-to-root-cause (the WHY latency): the
    # digest rides the metrics frames, so a TTRC blowup usually means
    # the publish->MR->recover path grew a stall, not the profiler.
    prior_rc = _prior_bench_value(
        repo_dir,
        r'"straggler\\?":.*?"negotiation\\?":\s*\{[^{}]*?'
        r'"ttrc_p50_s\\?":\s*([0-9.]+)')
    cur_ttrc = (cells.get("negotiation") or {}).get("ttrc_p50_s")
    if prior_rc is None or cur_ttrc is None:
        return  # first round with root-cause timing
    prior_ttrc, prior_rc_source = prior_rc
    rc_delta_pct = (cur_ttrc - prior_ttrc) \
        / max(prior_ttrc, 1e-9) * 100.0
    cur["ttrc_vs_prior"] = {
        "prior_ttrc_p50_s": prior_ttrc,
        "prior_source": prior_rc_source,
        "delta_pct": round(rc_delta_pct, 1),
        "tolerance_pct": tol_pct,
        "regressed": rc_delta_pct > tol_pct,
    }
    if cur["ttrc_vs_prior"]["regressed"]:
        print("WARNING: straggler time-to-root-cause regressed "
              "%.1f%% vs %s (%.3fs -> %.3fs), beyond the %.0f%% band"
              % (rc_delta_pct, prior_rc_source, prior_ttrc,
                 cur_ttrc, tol_pct), file=sys.stderr)


def bench_dlrm(args, smoke: bool) -> dict:
    """The recsys/DLRM-tiny lane at 8 CPU worker ranks (ROADMAP open
    item 5): model-parallel sharded embedding tables exchanged through
    the splits-piggybacking alltoall + a data-parallel dense MLP
    allreduced per step — the first benched workload whose hot loop is
    alltoall-dominated and whose splits change every step (the traffic
    steady-state replay legally cannot freeze).  Reports steps/s,
    per-rank alltoall GB/s, and the differential-checkpoint cost:
    full-base vs touched-rows-delta save latency and the
    delta_vs_full_bytes_ratio the Check-N-Run compression claim is
    gated on."""
    nproc = int(os.environ.get("HOROVOD_BENCH_DLRM_RANKS", "8"))
    data = _run_dlrm_workers(nproc, smoke)
    if "error" in data:
        return data
    data["platform"] = "cpu"
    # Tuned-vs-default pass: the DLRM loop is the sparse cycle-class
    # workload (three alltoalls per table per step + one dense
    # allreduce) — the pass proves the per-class search converges on
    # BOTH classes and reports the steps/s + alltoall GB/s deltas.
    # max_samples is capped so the grid force-converges inside the
    # lane's step budget.
    try:
        import tempfile
        prof = os.path.join(tempfile.mkdtemp(prefix="hvd-dlrm-tune-"),
                            "profile.json")
        tuned = _run_dlrm_workers(
            nproc, smoke, extra_env=_tune_env(prof, max_samples=6))
        if "error" not in tuned:
            d_sps, t_sps = data.get("steps_per_sec"), \
                tuned.get("steps_per_sec")
            d_gbps, t_gbps = data.get("alltoall_gbps"), \
                tuned.get("alltoall_gbps")
            try:
                with open(prof) as f:
                    profile = json.loads(f.read())
            except (OSError, ValueError):
                profile = None
            data["tuned_vs_default"] = {
                "tuned_steps_per_sec": t_sps,
                "steps_per_sec_delta_pct": round(
                    (t_sps - d_sps) / d_sps * 100.0, 1)
                if (t_sps and d_sps) else None,
                "alltoall_gbps_delta_pct": round(
                    (t_gbps - d_gbps) / d_gbps * 100.0, 1)
                if (t_gbps and d_gbps) else None,
                "profile_classes": sorted((profile or {}).get(
                    "classes") or []),
                "frozen": bool(profile),
            }
        else:
            data["tuned_vs_default"] = {"error": tuned["error"]}
    except Exception as e:
        data["tuned_vs_default"] = {"error": repr(e)[:300]}
    if args.only != "dlrm":
        data.pop("metrics", None)
    return data


def _run_dlrm_workers(nproc: int, smoke: bool, extra_env=None) -> dict:
    import shutil
    import tempfile

    from horovod_tpu.runner.http_server import RendezvousServer

    env = {"BENCH_DLRM_STEPS": "9" if smoke else "24"}
    env.update(extra_env or {})
    # Real multi-rank commit plane for the checkpoint section: one
    # rendezvous KV server in the parent carries the prepare marks and
    # the arbiter's commit record; the workers share one checkpoint
    # directory so rank 0 can gather every rank's shard into the
    # manifest it publishes.
    kv = RendezvousServer(verbose=0)
    kv_port = kv.start()
    cdir = tempfile.mkdtemp(prefix="hvd-dlrm-ckpt-")
    env.setdefault("BENCH_DLRM_KV", "127.0.0.1:%d" % kv_port)
    env.setdefault("BENCH_DLRM_CKPT_DIR", cdir)
    try:
        return _run_benchjson_workers(_DLRM_WORKER_SRC, nproc,
                                      extra_env=env, timeout=900)
    finally:
        kv.stop()
        shutil.rmtree(cdir, ignore_errors=True)


def _load_prior_dlrm(repo_dir: str):
    """Prior round's dlrm_tiny headline (same artifact walk as the
    smoke lane; older rounds predate the lane and simply miss)."""
    import glob
    arts = sorted(glob.glob(os.path.join(repo_dir, "BENCH_r*.json")))
    for path in reversed(arts):
        try:
            with open(path) as f:
                data = json.loads(f.read())
        except (OSError, ValueError):
            continue
        candidates = []
        if isinstance(data, dict):
            if isinstance(data.get("parsed"), dict):
                candidates.append(data["parsed"])
            candidates.append(data)
        for d in candidates:
            sec = d.get("dlrm_tiny")
            if isinstance(sec, dict) and sec.get("steps_per_sec"):
                spread = sec.get("steps_per_sec_spread") or [0, 0]
                lo, hi = float(spread[0] or 0), float(spread[-1] or 0)
                mid = float(sec["steps_per_sec"])
                return {"steps_per_sec": mid,
                        "spread_pct": (hi - lo) / mid * 100.0
                        if mid and hi >= lo else 0.0,
                        "source": os.path.basename(path)}
    return None


def check_dlrm_regression(out: dict, repo_dir: str):
    """Warn when the DLRM lane's steps/s regresses beyond measured
    noise vs the prior round, and record delta_vs_full_bytes_ratio in
    the comparison so the compression claim stays artifact-gated
    round over round (same mechanism as the smoke/recovery lanes)."""
    cur = out.get("dlrm_tiny") or {}
    cur_sps = cur.get("steps_per_sec")
    if not cur_sps:
        return
    spread = cur.get("steps_per_sec_spread") or [0, 0]
    cur_spread_pct = ((float(spread[-1]) - float(spread[0]))
                      / cur_sps * 100.0) if cur_sps else 0.0
    cmp = {"delta_vs_full_bytes_ratio":
           (cur.get("checkpoint") or {}).get(
               "delta_vs_full_bytes_ratio")}
    prior = _load_prior_dlrm(repo_dir)
    if prior is not None and prior["steps_per_sec"]:
        tol_pct = max(cur_spread_pct, prior["spread_pct"], 10.0)
        delta_pct = (cur_sps - prior["steps_per_sec"]) \
            / prior["steps_per_sec"] * 100.0
        cmp.update({
            "prior_steps_per_sec": prior["steps_per_sec"],
            "prior_source": prior["source"],
            "delta_pct": round(delta_pct, 1),
            "tolerance_pct": round(tol_pct, 1),
            "regressed": delta_pct < -tol_pct,
        })
        if cmp["regressed"]:
            print("WARNING: DLRM lane regressed %.1f%% vs %s "
                  "(%.2f -> %.2f steps/s), beyond the %.1f%% noise "
                  "band" % (-delta_pct, prior["source"],
                            prior["steps_per_sec"], cur_sps, tol_pct),
                  file=sys.stderr)
    ratio = cmp["delta_vs_full_bytes_ratio"]
    if ratio is not None and ratio > 0.1:
        print("WARNING: delta_vs_full_bytes_ratio %.3f exceeds the "
              "0.1 differential-checkpoint target at the DLRM-tiny "
              "touch rate" % ratio, file=sys.stderr)
    out["dlrm_vs_prior"] = cmp


def bench_serve(args, smoke: bool) -> dict:
    """The online-serving lane (docs/serving.md): 8 DLRM worker ranks
    train and commit differential checkpoints every few steps through
    the real KV commit protocol while a :class:`ServingReplica` in
    THIS process tails the manifest stream and answers a Zipf query
    load at a target QPS.  Reports read p50/p99, freshness lag
    p50/p99 (steps and seconds), achieved QPS, and the
    bit-consistency gate: a sample of served (step, ids, rows)
    triples is re-read from the committed chain after the run — every
    served row must equal the committed table at the served step."""
    import shutil
    import tempfile

    import numpy as np

    from horovod_tpu.checkpoint import assemble_table
    from horovod_tpu.common import metrics as _hm
    from horovod_tpu.models import dlrm_tiny_config
    from horovod_tpu.runner.http_server import RendezvousServer
    from horovod_tpu.serve import ServingReplica

    nproc = int(os.environ.get("HOROVOD_BENCH_SERVE_RANKS", "8"))
    qps = float(os.environ.get("HOROVOD_BENCH_SERVE_QPS", "50"))
    env = {"BENCH_SERVE_TRAIN_STEPS": "12" if smoke else "36",
           "BENCH_SERVE_CKPT_EVERY": "3",
           # Tail aggressively: the lane measures freshness lag, not
           # poll-interval quantisation.
           "HOROVOD_SERVE_POLL_SECONDS": "0.05"}
    os.environ["HOROVOD_SERVE_POLL_SECONDS"] = "0.05"
    kv = RendezvousServer(verbose=0)
    kv_port = kv.start()
    cdir = tempfile.mkdtemp(prefix="hvd-serve-ckpt-")
    env["BENCH_SERVE_KV"] = "127.0.0.1:%d" % kv_port
    env["BENCH_SERVE_CKPT_DIR"] = cdir
    cfg = dlrm_tiny_config()
    replica = None
    try:
        procs = _spawn_benchjson_workers(_SERVE_TRAINER_SRC, nproc,
                                         extra_env=env)
        # Bootstrap blocks on the FIRST committed manifest: serving
        # starts as soon as the trainer publishes, not after it exits.
        replica = ServingReplica(cdir)
        deadline = time.perf_counter() + 180.0
        while True:
            try:
                replica.bootstrap()
                break
            except Exception:
                if (time.perf_counter() > deadline
                        or any(p.poll() not in (None, 0)
                               for p in procs)):
                    raise
                time.sleep(0.05)
        replica.start()

        rng = np.random.default_rng(17)
        tables = ["dlrm.t%d" % i for i in range(cfg.num_tables)]
        lat_ms, fresh_steps, fresh_secs = [], [], []
        samples = []          # (step, table, ids, rows) for the gate
        period = 1.0 / max(qps, 1.0)
        t_begin = time.perf_counter()
        n_queries = 0
        while any(p.poll() is None for p in procs):
            t_next = t_begin + n_queries * period
            now = time.perf_counter()
            if now < t_next:
                time.sleep(min(t_next - now, period))
            ids = ((rng.zipf(1.3, size=16) - 1)
                   % cfg.table_rows[0]).astype(np.int64)
            table = tables[n_queries % len(tables)]
            t0 = time.perf_counter()
            rows, step = replica.lookup(table, ids)
            lat_ms.append((time.perf_counter() - t0) * 1e3)
            served, latest = replica.freshness()
            fresh_steps.append(max((latest or served) - served, 0))
            g = _hm.snapshot()["gauges"].get(
                "hvd_serve_freshness_seconds")
            if g is not None:
                fresh_secs.append(float(g))
            if n_queries % 7 == 0 and len(samples) < 64:
                samples.append((step, table, ids.copy(), rows.copy()))
            n_queries += 1
        wall = time.perf_counter() - t_begin
        data = _drain_benchjson_workers(procs, timeout=900)
        if "error" in data:
            return data
        replica.stop()

        # Bit-consistency gate: replay each sampled step's committed
        # chain through a FRESH read-only manager and compare the
        # served rows against the assembled table at that step.
        from horovod_tpu.checkpoint import CheckpointManager
        ro = CheckpointManager(cdir, rank=0, world_size=1, keep=None)
        assembled = {}
        mismatches = 0
        for step, table, ids, rows in samples:
            key = (step, table)
            if key not in assembled:
                items = ro.restore(step)
                assembled[key] = assemble_table(
                    items, "sparse/%s/rows" % table)
            if not np.array_equal(assembled[key][ids], rows):
                mismatches += 1
        ro.close()

        def _pct(xs, q):
            return round(float(np.percentile(xs, q)), 3) if xs else None

        data["platform"] = "cpu"
        data["query"] = {
            "target_qps": qps,
            "achieved_qps": round(n_queries / wall, 1) if wall else 0,
            "queries": n_queries,
            "read_p50_ms": _pct(lat_ms, 50),
            "read_p99_ms": _pct(lat_ms, 99),
            "freshness_steps_p50": _pct(fresh_steps, 50),
            "freshness_steps_p99": _pct(fresh_steps, 99),
            "freshness_seconds_p50": _pct(fresh_secs, 50),
            "freshness_seconds_p99": _pct(fresh_secs, 99),
        }
        data["bit_consistency"] = {
            "verified": len(samples),
            "mismatches": mismatches,
            "ok": bool(samples) and mismatches == 0,
        }
        snap = _hm.snapshot()
        data["serve_metrics"] = {
            "rows_total": snap["counters"].get("hvd_serve_rows_total"),
            "snapshot_flips_total":
                snap["counters"].get("hvd_serve_snapshot_flips_total"),
        }
        return data
    finally:
        if replica is not None:
            replica.stop()
        kv.stop()
        shutil.rmtree(cdir, ignore_errors=True)


def _load_prior_serve(repo_dir: str):
    """Prior round's serve-lane read p99 (same artifact walk as the
    other lanes; older rounds predate the lane and simply miss)."""
    import glob
    arts = sorted(glob.glob(os.path.join(repo_dir, "BENCH_r*.json")))
    for path in reversed(arts):
        try:
            with open(path) as f:
                data = json.loads(f.read())
        except (OSError, ValueError):
            continue
        candidates = []
        if isinstance(data, dict):
            if isinstance(data.get("parsed"), dict):
                candidates.append(data["parsed"])
            candidates.append(data)
        for d in candidates:
            q = ((d.get("serve") or {}).get("query")
                 if isinstance(d.get("serve"), dict) else None)
            if isinstance(q, dict) and q.get("read_p99_ms"):
                return {"read_p99_ms": float(q["read_p99_ms"]),
                        "source": os.path.basename(path)}
    return None


def check_serve_regression(out: dict, repo_dir: str):
    """Warn when the serving lane's read p99 regresses >2x vs the
    prior round, and FAIL LOUDLY (stderr warning, recorded flag) when
    the bit-consistency gate caught a torn or stale-row read — that is
    the lane's whole reason to exist."""
    cur = out.get("serve") or {}
    gate = cur.get("bit_consistency") or {}
    cmp = {"bit_consistency_ok": gate.get("ok")}
    if gate and not gate.get("ok"):
        print("WARNING: serve lane bit-consistency gate FAILED: "
              "%s mismatches out of %s verified served reads"
              % (gate.get("mismatches"), gate.get("verified")),
              file=sys.stderr)
    p99 = (cur.get("query") or {}).get("read_p99_ms")
    prior = _load_prior_serve(repo_dir)
    if p99 and prior is not None and prior["read_p99_ms"]:
        ratio = p99 / prior["read_p99_ms"]
        cmp.update({"read_p99_ms": p99,
                    "prior_read_p99_ms": prior["read_p99_ms"],
                    "prior_source": prior["source"],
                    "ratio": round(ratio, 2),
                    "regressed": ratio > 2.0})
        if cmp["regressed"]:
            print("WARNING: serve lane read p99 regressed %.1fx vs "
                  "%s (%.2f -> %.2f ms)" % (
                      ratio, prior["source"], prior["read_p99_ms"],
                      p99), file=sys.stderr)
    out["serve_vs_prior"] = cmp


def _load_prior_smoke(repo_dir: str):
    """Smoke headline (images_per_sec, spread_pct, source file) from
    the most recent prior round's BENCH_r*.json.  Driver artifacts wrap
    the bench JSON ({"rc", "tail", "parsed", ...}) and the tail may be
    truncated at the front, so fall back to regexing the smoke section
    out of the text."""
    import glob
    import re
    arts = sorted(glob.glob(os.path.join(repo_dir, "BENCH_r*.json")))
    for path in reversed(arts):
        try:
            with open(path) as f:
                raw = f.read()
            data = json.loads(raw)
        except (OSError, ValueError):
            continue
        candidates = []
        if isinstance(data, dict):
            if isinstance(data.get("parsed"), dict):
                candidates.append(data["parsed"])
            candidates.append(data)  # a bare bench JSON line
        for d in candidates:
            smoke = d.get("resnet18_smoke")
            if isinstance(smoke, dict) and smoke.get("images_per_sec"):
                return {"images_per_sec": smoke["images_per_sec"],
                        "spread_pct": smoke.get("spread_pct", 0.0),
                        "source": os.path.basename(path)}
        m = re.search(
            r'\\?"resnet18_smoke\\?":\s*\{(.*?)\}', raw, re.S)
        if m:
            body = m.group(1).replace("\\\"", "\"")
            img = re.search(r'"images_per_sec":\s*([0-9.]+)', body)
            spread = re.search(r'"spread_pct":\s*([0-9.]+)', body)
            # Zero headline = a failed prior smoke; useless (and
            # divide-by-zero-dangerous) as a baseline — keep looking.
            if img and float(img.group(1)) > 0:
                return {"images_per_sec": float(img.group(1)),
                        "spread_pct": float(spread.group(1))
                        if spread else 0.0,
                        "source": os.path.basename(path)}
    return None


def check_smoke_regression(out: dict, repo_dir: str):
    """Warn when the CPU smoke headline regresses by more than its own
    measured noise vs the prior round's artifact (round-5 lesson: a
    13% smoke regression shipped silently because nothing compared
    rounds).  The tolerance is the LARGER of the two runs' spread_pct
    (never below 5%): a drop inside scheduler noise is not a finding.
    Records the comparison in the artifact either way."""
    cur = out.get("resnet18_smoke") or {}
    cur_img = cur.get("images_per_sec")
    if not cur_img:
        return
    prior = _load_prior_smoke(repo_dir)
    if prior is None or not prior["images_per_sec"]:
        return
    tol_pct = max(float(cur.get("spread_pct") or 0.0),
                  float(prior["spread_pct"] or 0.0), 5.0)
    delta_pct = (cur_img - prior["images_per_sec"]) \
        / prior["images_per_sec"] * 100.0
    cmp = {
        "prior_images_per_sec": prior["images_per_sec"],
        "prior_source": prior["source"],
        "delta_pct": round(delta_pct, 1),
        "tolerance_pct": round(tol_pct, 1),
        "regressed": delta_pct < -tol_pct,
    }
    out["smoke_vs_prior"] = cmp
    if cmp["regressed"]:
        print("WARNING: CPU smoke headline regressed %.1f%% vs %s "
              "(%.2f -> %.2f img/s), beyond the %.1f%% noise band"
              % (-delta_pct, prior["source"],
                 prior["images_per_sec"], cur_img, tol_pct),
              file=sys.stderr)


def _lane_errors(node, path=""):
    """Paths of every dict under ``node`` that carries an ``error``."""
    if not isinstance(node, dict):
        return []
    found = [path] if node.get("error") else []
    for key, value in node.items():
        found += _lane_errors(value, path + "/" + key if path else key)
    return found


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true",
                   help="tiny CPU-friendly run for CI")
    p.add_argument("--batch-size", type=int, default=None)
    # Swept on v5e: 64 beats 32 (553.8 vs 528.9 samples/s, 73.9% vs
    # 70.6% MFU) and 128 (524.2).
    p.add_argument("--bert-batch", type=int, default=64)
    p.add_argument("--bert-seq", type=int, default=128)
    p.add_argument("--num-iters", type=int, default=50)
    p.add_argument("--warmup", type=int, default=5)
    p.add_argument("--only",
               choices=["resnet", "bert", "keras",
                        "collectives", "checkpoint", "scale",
                        "recovery", "autoscale", "dlrm",
                        "coordscale", "blackbox", "tune",
                        "straggler", "serve"],
                   default=None)
    args = p.parse_args()

    import jax
    from horovod_tpu.common import compile_cache
    if args.smoke:
        jax.config.update("jax_platforms", "cpu")
    compile_cache.enable()

    dev = jax.devices()[0]
    if not args.smoke and dev.platform != "tpu":
        # A measurement path that finds no chip fails; --smoke is the
        # CPU variant.
        print("bench.py measures on the TPU and found platform %r (%s); "
              "run `python bench.py --smoke` for the CPU smoke variant"
              % (dev.platform, dev.device_kind), file=sys.stderr)
        return 1
    out = {
        "device": {"kind": dev.device_kind,
                   "platform": dev.platform,
                   "count": len(jax.devices()),
                   "peak_bf16_tflops": None if args.smoke
                   else peak_bf16_tflops(dev)},
    }

    # CPU-contention context for every timed section below: a non-idle
    # load average before the benches start means the numbers carry a
    # rig tax.
    try:
        out["cpu"] = {"count": os.cpu_count(),
                      "load_avg_start": [round(x, 2)
                                         for x in os.getloadavg()]}
    except OSError:
        pass

    run = {args.only} if args.only else {"resnet", "bert", "keras",
                                     "collectives", "checkpoint",
                                     "scale", "recovery", "autoscale",
                                     "dlrm", "coordscale", "blackbox",
                                     "tune", "straggler", "serve"}

    resnet = {}
    if "resnet" in run:
        key = "resnet50" if not args.smoke else "resnet18_smoke"
        try:
            resnet = bench_resnet(args, args.smoke)
            out[key] = resnet
        except Exception as e:
            out[key] = {"error": repr(e)[:300]}
    if "bert" in run:
        key = "bert_large" if not args.smoke else "bert_tiny_smoke"
        try:
            out[key] = bench_bert(args, args.smoke)
        except Exception as e:  # OOM on small chips must not kill the run
            out[key] = {"error": repr(e)[:300]}
    if "keras" in run:
        key = "keras_mnist_jax" if not args.smoke \
            else "keras_mnist_jax_smoke"
        try:
            out[key] = bench_keras_jax(args, args.smoke)
        except Exception as e:
            out[key] = {"error": repr(e)[:300]}
    if "checkpoint" in run:
        key = "checkpoint" if not args.smoke else "checkpoint_smoke"
        try:
            out[key] = bench_checkpoint(args, args.smoke)
        except Exception as e:
            out[key] = {"error": repr(e)[:300]}
    if "collectives" in run:
        sizes = [1] if args.smoke else [1, 4, 16, 64, 256]
        try:
            out["allreduce_eager"] = bench_collectives(sizes)
            # XLA-mesh control lane at 1 MB: quantifies, in the same
            # artifact, why the native ring (+shm) is the CPU default
            # (per-call compiled-collective dispatch costs ms).
            try:
                xla = bench_collectives([1], plane="XLA")
                out["allreduce_eager"]["xla_control_1mb"] = {
                    "gbps": next((r["gbps"] for r in
                                  xla.get("results", [])
                                  if r["input"] == "numpy"), None),
                    "tiny_allreduce_ms": xla.get(
                        "control_floor", {}).get("tiny_allreduce_ms"),
                    "error": xla.get("error"),
                }
            except Exception as e:
                out["allreduce_eager"]["xla_control_1mb"] = {
                    "error": repr(e)[:200]}
        except Exception as e:
            out["allreduce_eager"] = {"error": repr(e)[:300]}
    if "scale" in run:
        try:
            out["scale_eager"] = bench_scale(args, args.smoke)
        except Exception as e:
            out["scale_eager"] = {"error": repr(e)[:300]}
    if "recovery" in run:
        try:
            out["recovery"] = bench_recovery(args, args.smoke)
        except Exception as e:
            out["recovery"] = {"error": repr(e)[:300]}
        check_recovery_regression(
            out, os.path.dirname(os.path.abspath(__file__)))
    if "autoscale" in run:
        try:
            out["autoscale"] = bench_autoscale(args, args.smoke)
        except Exception as e:
            out["autoscale"] = {"error": repr(e)[:300]}
        check_autoscale_regression(
            out, os.path.dirname(os.path.abspath(__file__)))
    if "dlrm" in run:
        try:
            out["dlrm_tiny"] = bench_dlrm(args, args.smoke)
        except Exception as e:
            out["dlrm_tiny"] = {"error": repr(e)[:300]}
        check_dlrm_regression(
            out, os.path.dirname(os.path.abspath(__file__)))
    if "coordscale" in run:
        try:
            out["coord_scale"] = bench_coord_scale(args, args.smoke)
        except Exception as e:
            out["coord_scale"] = {"error": repr(e)[:300]}
        check_coord_scale_regression(
            out, os.path.dirname(os.path.abspath(__file__)))
    if "blackbox" in run:
        try:
            out["blackbox"] = bench_blackbox(args, args.smoke)
        except Exception as e:
            out["blackbox"] = {"error": repr(e)[:300]}
        check_blackbox_regression(
            out, os.path.dirname(os.path.abspath(__file__)))
    if "tune" in run:
        try:
            out["tune"] = bench_tune(args, args.smoke)
        except Exception as e:
            out["tune"] = {"error": repr(e)[:300]}
        check_tune_regression(
            out, os.path.dirname(os.path.abspath(__file__)))
    if "straggler" in run:
        try:
            out["straggler"] = bench_straggler(args, args.smoke)
        except Exception as e:
            out["straggler"] = {"error": repr(e)[:300]}
        check_straggler_regression(
            out, os.path.dirname(os.path.abspath(__file__)))
    if "serve" in run:
        try:
            out["serve"] = bench_serve(args, args.smoke)
        except Exception as e:
            out["serve"] = {"error": repr(e)[:300]}
        check_serve_regression(
            out, os.path.dirname(os.path.abspath(__file__)))

    if args.smoke:
        check_smoke_regression(
            out, os.path.dirname(os.path.abspath(__file__)))
        check_ckpt_regression(
            out, os.path.dirname(os.path.abspath(__file__)))
    img_sec = resnet.get("images_per_sec", 0.0)
    out.update({
        "metric": "resnet50_images_per_sec_per_chip" if not args.smoke
                  else "resnet18_smoke_images_per_sec",
        "value": img_sec,
        "unit": "images/sec",
        "vs_baseline": round(img_sec / REFERENCE_IMG_SEC_PER_DEVICE, 3),
    })
    print(json.dumps(out))
    # Every lane catches its own exception so the others still report;
    # the run as a whole fails if any did.
    failed = sorted(_lane_errors(out))
    if failed:
        print("bench.py: lanes reported an error: %s" % ", ".join(failed),
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
