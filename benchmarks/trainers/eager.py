"""The eager trainer: one process a chip, the loop a Horovod-on-JAX user
writes (``examples/adasum/adasum_bert_pretrain.py``): a jitted
``loss_and_grads``, ``tx.update`` through ``hvd.jax.DistributedOptimizer``
and ``optax.apply_updates``, both outside jit, every ``HOROVOD_*`` knob
at its default.

Started by ``run.py`` under ``python -m horovod_tpu.runner.launch -np
<processes>``.  Every rank takes the same number of steps: rank 0 fixes
it before the window from the warm-up's step time and broadcasts it, so
no rank waits at a collective the others never enter.
"""

import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402

from benchmarks import spec  # noqa: E402
from benchmarks.reference import common as reference  # noqa: E402
from benchmarks.trainers import common  # noqa: E402
from benchmarks.trainers.common import info, span  # noqa: E402

# Summing four float32 gradients in another order than numpy's.
EXCHANGE_RTOL, EXCHANGE_ATOL = 1e-5, 1e-7


class Stamps:
    """What the benchmark's own inner optimizer records: the clock at
    its entry (the end of ``allreduce_gradients``) and, when asked, the
    gradients it was handed."""

    def __init__(self):
        self.called = 0.0
        self.exchange_s = []
        self.exchange_span = None
        self.keep_leaves = None
        self.reduced = None


def stamping(inner: optax.GradientTransformation, stamps: Stamps):
    """``inner`` with a stamp at the entry of its ``update``."""
    def update(grads, state, params=None, **extra):
        stamps.exchange_s.append(time.perf_counter() - stamps.called)
        if stamps.exchange_span is not None:
            stamps.exchange_span.__exit__(None, None, None)
            stamps.exchange_span = None
        if stamps.keep_leaves:
            stamps.reduced = {n: reference.get_leaf(grads, n)
                              for n in stamps.keep_leaves}
        with span("optimizer"):
            return inner.update(grads, state, params, **extra)
    return optax.GradientTransformation(inner.init, update)


def counter_total(snapshot: dict, name: str) -> float:
    value = snapshot["counters"].get(name, 0.0)
    return float(sum(value.values()) if isinstance(value, dict) else value)


def main(argv=None, platform: str = "tpu", root: str = spec.HERE):
    args = common.parse_args(argv)
    watch = common.CompileWatch()
    import horovod_tpu as hvd
    import horovod_tpu.jax as hj
    from horovod_tpu.common import compile_cache

    hvd.init()
    init_s = time.time() - args.t0
    cache_dir = compile_cache.enable()
    cell = spec.Cell(args.workload, root)
    rank, size = hvd.rank(), hvd.size()
    if size != cell.processes or size != cell.chips:
        raise RuntimeError("the cell wants %d processes on %d chips, the "
                           "world has %d" % (cell.processes, cell.chips,
                                             size))
    device = common.require_device(platform, cell.chips)
    if jax.local_device_count() != 1:
        raise RuntimeError("rank %d holds %d devices, not one"
                           % (rank, jax.local_device_count()))
    family = spec.load_family(cell.config)
    t = cell.traffic
    if rank == 0:
        info("cell %s: config %s, traffic %s, %d ranks, %d x %d a chip, "
             "cache %s" % (cell.name, cell.config_name, cell.traffic_name,
                           size, t["batch_per_chip"], t["seq_len"],
                           cache_dir))

    def rank0_first(call):
        """Rank 0 makes the first call of a program (and leaves it in
        the persistent cache) while the others wait; then they call."""
        if rank == 0:
            out = jax.block_until_ready(call())
            hj.barrier()
        else:
            hj.barrier()
            out = jax.block_until_ready(call())
        return out

    pool = common.batch_pool(family, cell, args.seed, rank)
    parts = {}
    t0 = time.perf_counter()
    init = jax.jit(lambda key, batch: family.init_params(
        cell.config, key, batch))
    params = rank0_first(lambda: init(jax.random.PRNGKey(args.seed),
                                      pool[0]))
    parts["init_program_s"] = time.perf_counter() - t0
    params = hj.broadcast_parameters(params, root_rank=0)
    n_leaves = len(jax.tree.leaves(params))
    n_params = sum(p.size for p in jax.tree.leaves(params))

    t0 = time.perf_counter()
    ref_ok = True
    if rank == 0:
        info("%d parameters in %d leaves" % (n_params, n_leaves))
        ref_ok, _ = common.reference_check(family, cell, params, args.seed)
    parts["reference_check_s"] = time.perf_counter() - t0

    stamps = Stamps()
    inner = family.optimizer(cell.config)
    tx = hj.DistributedOptimizer(stamping(inner, stamps),
                                 op=getattr(hvd, t["reduce_op"]))
    opt_state = tx.init(params)
    train_loss = family.train_loss(cell.config)
    loss_and_grads = jax.jit(jax.value_and_grad(train_loss))

    t0 = time.perf_counter()
    rank0_first(lambda: loss_and_grads(params, pool[0], np.int32(0)))
    parts["first_grad_s"] = time.perf_counter() - t0

    losses = []
    n = 0

    def one_step(params, opt_state, update, fetch: bool):
        """One iteration of the user's loop.  ``fetch`` reads the loss
        right after the gradient program, which makes the host wait for
        the gradients before the exchange starts."""
        nonlocal n
        with span("input"):
            batch = jax.device_put(pool[n % len(pool)])
        with span("grad"):
            loss, grads = loss_and_grads(params, batch, np.int32(n))
        if fetch:
            with span("fetch"):
                float(loss)
        stamps.exchange_span = span("exchange")
        stamps.exchange_span.__enter__()
        stamps.called = time.perf_counter()
        updates, opt_state = update(grads, opt_state, params)
        with span("optimizer"):
            params = optax.apply_updates(params, updates)
        losses.append(loss)
        n += 1
        return params, opt_state, loss

    # ``warmup_steps`` steps, each timed to its end; whether replay had
    # engaged by then and whether the last one still compiled is
    # printed, not waited for (on the chip neither had settled).
    warm, entries0 = [], counter_total(hvd.metrics_snapshot(),
                                       "hvd_steady_state_entries")
    for _ in range(t["warmup_steps"]):
        t0, requests = time.perf_counter(), watch.requests
        params, opt_state, loss = one_step(params, opt_state, tx.update,
                                           fetch=True)
        jax.block_until_ready(params)
        warm.append(time.perf_counter() - t0)
        engaged = counter_total(hvd.metrics_snapshot(),
                                "hvd_steady_state_entries") > entries0
    parts["first_step_s"] = warm[0]
    parts["steady_step_s"] = float(np.median(warm[len(warm) // 2:]))
    parts["warmup_s"] = sum(warm)
    if rank == 0:
        info("warm-up steps %s; replay engaged: %s; last step compiled: %s"
             % (["%.3f" % w for w in warm], engaged,
                watch.requests > requests))

    # The same loop without the exchange, for eager_efficiency; its
    # results are dropped so that the ranks stay in step.
    local_step_s = []
    if args.trace:
        plain = stamping(inner, Stamps())
        t0 = time.perf_counter()
        for _ in range(t["local_steps"]):
            s0 = time.perf_counter()
            p2, _, _ = one_step(params, opt_state, plain.update, fetch=True)
            jax.block_until_ready(p2)
            local_step_s.append(time.perf_counter() - s0)
            losses.pop()
            n -= 1
        del p2
        parts["local_steps_s"] = time.perf_counter() - t0

    # Rank 0 fixes the number of steps for every rank: the whole groups
    # that fill the window at the warm-up's step time.
    group = 1 if args.trace else t["fetch_every"]
    groups = math.ceil(args.seconds / parts["steady_step_s"] / group)
    steps = int(np.asarray(hvd.broadcast(
        jnp.asarray([groups * group], jnp.int32), root_rank=0,
        name="bench.steps"))[0])

    stamps.exchange_s.clear()
    dispatched0 = counter_total(hvd.metrics_snapshot(),
                                "hvd_responses_dispatched_total")
    requests_before = watch.requests
    setup_s = time.time() - args.t0
    step_s, trace = [], None
    w0 = time.perf_counter()
    for i in range(steps):
        s0 = time.perf_counter()
        params, opt_state, loss = one_step(
            params, opt_state, tx.update, fetch=bool(args.trace))
        if not args.trace and (i + 1) % group == 0:
            with span("fetch"):
                float(loss)
        step_s.append(time.perf_counter() - s0)
    jax.block_until_ready(params)
    window_s = time.perf_counter() - w0
    window_compiles = watch.check_window(requests_before, strict=False)
    exchange_s = list(stamps.exchange_s)
    dispatched = counter_total(hvd.metrics_snapshot(),
                               "hvd_responses_dispatched_total") - dispatched0
    if args.trace:
        # A few steps as the measured loop runs them; only rank 0
        # traces, every rank steps.
        batch_like = jax.device_put(pool[0])
        tracer = common.Tracer(args.out) if rank == 0 else None
        if tracer:
            tracer.__enter__()
        with span("window"):
            for _ in range(t["traced_steps"]):
                params, opt_state, loss = one_step(
                    params, opt_state, tx.update, fetch=False)
            with span("fetch"):
                float(loss)
                jax.block_until_ready(params)
        if tracer:
            tracer.__exit__(None, None, None)
            trace = tracer.reduce(loss_and_grads.lower(
                params, batch_like, np.int32(0)).compile().as_text())

    band_step = cell.workload["loss_band"]["step"]
    while n < band_step:
        params, opt_state, loss = one_step(params, opt_state, tx.update,
                                           fetch=False)

    # Correctness condition (3): one more step whose local and reduced
    # gradients are kept for three leaves, then the parameters' sums.
    names = cell.config["check_leaves"]
    stamps.keep_leaves = names
    batch = jax.device_put(pool[n % len(pool)])
    loss, grads = loss_and_grads(params, batch, np.int32(n))
    local = {k: reference.get_leaf(grads, k) for k in names}
    updates, opt_state = tx.update(grads, opt_state, params)
    params = optax.apply_updates(params, updates)
    losses.append(loss)
    n += 1
    del grads, updates
    exchange_ok = True
    for i, k in enumerate(names):
        gathered = np.asarray(hvd.allgather(local[k][None],
                                            name="bench.check.%d" % i))
        want = gathered.mean(axis=0)
        got = np.asarray(stamps.reduced[k])
        close = np.allclose(got, want, rtol=EXCHANGE_RTOL,
                            atol=EXCHANGE_ATOL * np.abs(want).max())
        exchange_ok = exchange_ok and close
        if rank == 0:
            info("exchange check %s: max abs diff %.3e of max %.3e: %s"
                 % (k, np.abs(got - want).max(), np.abs(want).max(),
                    "ok" if close else "FAILED"))
    sums = jax.jit(lambda p: jnp.stack(
        [jnp.sum(leaf.astype(jnp.float32)) for leaf in jax.tree.leaves(p)]))(
            params)
    all_sums = np.asarray(hvd.allgather(sums[None], name="bench.checksum"))
    checksum_ok = bool((all_sums == all_sums[0]).all())

    host_losses = [float(v) for v in jax.device_get(losses)]
    failed = int(np.sum(~np.isfinite(host_losses)))
    peak = common.peak_bytes()
    mine = jnp.asarray([[failed, watch.programs_compiled, peak >> 20,
                         peak & ((1 << 20) - 1),
                         int(common.on_platform((params, opt_state),
                                                platform))]], jnp.int32)
    ranks = np.asarray(hvd.allgather(mine, name="bench.ranks"))
    if rank == 0:
        info("per rank [non-finite losses, programs compiled, peak MiB, "
             "peak B, on %s]: %s" % (platform, ranks.tolist()))
        info("parameter checksums equal on all ranks: %s; exchange equals "
             "the numpy mean: %s" % (checksum_ok, exchange_ok))
        fullest = ranks[np.argmax(ranks[:, 2] * float(1 << 20)
                                  + ranks[:, 3])]
        device["memory_peak_bytes"] = int(fullest[2]) * (1 << 20) + \
            int(fullest[3])
        failed = int(ranks[:, 0].max())
        correct = (ref_ok and failed == 0 and bool(ranks[:, 4].all())
                   and checksum_ok and exchange_ok
                   and common.loss_band_ok(cell, host_losses))
        run = {
            "cell": cell.name, "seed": args.seed, "device": device,
            "correct": correct, "attempted": n, "failed": failed,
            "steps": steps, "window_s": window_s, "losses": host_losses,
            "setup_s": setup_s, "init_s": init_s, "setup_parts": parts,
            "compile_s": (parts["init_program_s"] + parts["first_grad_s"]
                          + parts["first_step_s"]
                          - parts["steady_step_s"]),
            "programs_compiled": watch.programs_compiled,
            "programs_compiled_by_rank": ranks[:, 1].tolist(),
            "compiled_s": watch.compiled, "loaded_s": watch.loaded,
            "window_compiles": window_compiles,
            "step_s": step_s if args.trace else [],
            "local_step_s": local_step_s, "exchange_s": exchange_s,
            "responses_dispatched": dispatched, "replay_engaged": engaged,
            "traced_steps": t["traced_steps"], "trace": trace,
            "n_params": n_params, "n_leaves": n_leaves,
        }
        common.finish(args, cell, family, run, root)
    hvd.shutdown()


if __name__ == "__main__":
    main()
