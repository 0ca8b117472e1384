"""Times the routed experts' wide passes alone, on the chip: ns a row.

    chiprun -- python tools/moe_pass_bench.py

For each sparse cell's shapes (tokens, top k, experts, held, hidden,
width) a random routing is planned (``parallel/moe.py`` ``held_pairs``)
and each pass is timed in both forms (XLA's, the kernels'), the token
side's packing by XLA beside the pack kernel's (a row copied HBM to HBM
instead of into VMEM cost 18 us, not 50 ns, when PR 41 tried it); the
three grouped products (the product and its two transposes, into the
experts' width and out of it) as ``lax.ragged_dot`` and as the kernels
at each row tile of ``--tiles``, at the cells' shapes and at 2048 and
4096 rows an expert (``DEPLOYED``: what a deployment's chip gets);
then one layer, forward and backward, on the host's clock and by every
operation's self time on the device.  Every kernel's rows that hold a
pair are held to
the XLA form's, bit for bit.  ``--tiny`` runs the same code at a toy
size in the Pallas interpreter, to rehearse it off the chip.  Prints a
table and writes ``chiprun_out/moe_pass_bench.json`` (``_tiny`` there).
"""

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from horovod_tpu.ops import pallas_moe
from horovod_tpu.parallel import moe

# tokens, top k, experts, held, hidden, width
CELLS = {"qwen3-next": (8192, 10, 512, 32, 2048, 512),
         "kanana": (16384, 6, 128, 16, 2048, 768),
         "lfm2": (8192, 4, 64, 16, 2048, 1536),
         "trinity-mini": (16384, 8, 128, 16, 2048, 1024)}
# LFM2's layer with four and eight times the tokens: the grouped
# products alone are timed there.
DEPLOYED = {"2048-rows-an-expert": (32768, 4, 64, 16, 2048, 1536),
            "4096-rows-an-expert": (65536, 4, 64, 16, 2048, 1536)}
FORMS = ("xla", "kernel")
TINY = {"tiny": (64, 2, 16, 4, 2048, 128)}
PEAK_FLOPS = 197e12     # a v5e chip's, bfloat16


def timed(fn, *args, iters=20):
    """Milliseconds a call, the calls queued behind one another."""
    out = fn(*args)
    jax.block_until_ready(out)
    start = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / iters * 1e3, out


def plan_of(key, tokens, top_k, experts, held):
    # an expert's own offset skews the loads as a trained router's are
    logits = (jax.random.normal(key, (tokens, experts))
              + jax.random.normal(jax.random.fold_in(key, 1), (experts,)))
    gates, chosen = lax.top_k(jax.nn.softmax(logits), top_k)
    return jax.jit(moe.held_pairs, static_argnums=(1, 2))(
        moe.Routing(chosen.astype(jnp.int32), gates), 0, held)


def same_rows(got, want, n):
    return bool((np.asarray(got[:n].astype(jnp.float32))
                 == np.asarray(want[:n].astype(jnp.float32))).all())


def passes(name, shapes, dtype, tiny, lines):
    tokens, top_k, experts, held, hidden, width = shapes
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    plan, row_gate = plan_of(keys[0], tokens, top_k, experts, held)
    pairs = plan.group_sizes.sum()
    rows, n = plan.token.shape[0], int(pairs)
    held_first = jax.jit(moe._pairs_held_first)(plan)
    x = jax.random.normal(keys[1], (tokens, hidden), dtype)
    out = jax.random.normal(keys[2], (rows, hidden), dtype)
    a, b, d = (jax.random.normal(k, (rows, width), dtype) for k in keys[3:])

    def record(what, form, ms, **more):
        line = dict(cell=name, rows=rows, pairs=n, what=what, form=form,
                    ms=round(ms, 4), ns_a_row=round(ms * 1e6 / rows, 1),
                    ns_a_pair=round(ms * 1e6 / max(n, 1), 1), **more)
        lines.append(line)
        print(json.dumps(line), flush=True)

    xla = lambda f: jax.jit(lambda v: f(v, plan, False))
    ms, want_rows = timed(xla(moe._rows_of_tokens), x)
    record("rows_of_tokens", "xla", ms)
    ms, want_y = timed(xla(moe._combine), out)
    record("tokens_of_rows", "xla", ms)
    ms, _ = timed(lambda: pallas_moe.pack_rows(out, pairs))
    record("pack_rows (the buffer, to the pairs' end)", "kernel", ms)
    ms, _ = timed(lambda: pallas_moe.pack_rows(x, tokens))
    record("pack_rows (the tokens)", "kernel", ms)
    ms, _ = timed(jax.jit(pallas_moe.packed_by_xla), x)
    record("packed_by_xla (the tokens)", "xla", ms)
    ms, _ = timed(lambda: pallas_moe.add_rows(out, out, pairs))
    record("add_rows", "kernel", ms)
    ms, _ = timed(jax.jit(lambda a, b: a + b), out, out)
    record("add_rows", "xla", ms)
    ms, got = timed(lambda: pallas_moe.rows_of_tokens(x, plan.token, pairs))
    record("rows_of_tokens", "kernel", ms, same=same_rows(got, want_rows, n))
    ms, got = timed(lambda: pallas_moe.tokens_of_rows(
        out, *held_first, pairs))
    record("tokens_of_rows", "kernel", ms,
           same=same_rows(got, want_y, tokens))

    def gated_xla(a, b):
        keep = plan.valid[:, None]
        g = jax.nn.silu(jnp.where(keep, a, 0)) * jnp.where(keep, b, 0)
        return (g.astype(jnp.float32) * row_gate[:, None]).astype(a.dtype)
    ms, _ = timed(jax.jit(gated_xla), a, b)
    record("gated", "xla", ms)
    ms, _ = timed(jax.jit(lambda a, b, d: jax.vjp(gated_xla, a, b)[1](d)),
                  a, b, d)
    record("gated backward", "xla", ms)
    ms, _ = timed(lambda: pallas_moe.gated(a, b, row_gate, pairs))
    record("gated", "kernel", ms)
    ms, _ = timed(lambda: pallas_moe.gated_bwd(a, b, row_gate, d, pairs))
    record("gated backward", "kernel", ms)


def device_events(step, args, iters):
    """The device's events of ``iters`` traced calls of the jitted
    ``step``, with their module paths (``benchmarks/trace_reduce.py``),
    and the last call's result."""
    import glob
    import tempfile
    from benchmarks import trace_reduce
    names = trace_reduce.op_names(step.lower(*args).compile().as_text())
    out = jax.block_until_ready(step(*args))
    with tempfile.TemporaryDirectory() as trace_dir:
        jax.profiler.start_trace(trace_dir)
        for _ in range(iters):
            out = step(*args)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        path, = glob.glob(trace_dir + "/plugins/profile/*/*.xplane.pb")
        events = trace_reduce.load_events(path, names)["device"]
    return list(zip(events, trace_reduce.self_times(events))), out


def device_ms(fn, *args, iters=10):
    """Milliseconds a call of ``fn`` by the device's own clock: every
    operation's self time over ``iters`` traced calls (the host's clock
    around queued calls reads its own dispatch, 0.2 ms, for a kernel
    shorter than that); off the TPU the host's clock."""
    if jax.devices()[0].platform != "tpu":
        return timed(fn, *args, iters=2)
    events, out = device_events(jax.jit(fn), args, iters)
    return sum(own for _, own in events) / iters / 1e6, out


def grouped(name, shapes, dtype, tiles, interpret, lines):
    """The grouped product and its two transposes, into the experts'
    width and out of it: ``lax.ragged_dot`` and its VJP against the
    three kernels at each row tile; ms a call on the device's clock and
    the share of the peak on the pairs' work."""
    tokens, top_k, experts, held, hidden, width = shapes
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    plan, _ = plan_of(keys[0], tokens, top_k, experts, held)
    sizes, rows = plan.group_sizes, plan.token.shape[0]
    n = int(sizes.sum())
    for k, cols, way in ((hidden, width, "into the width"),
                         (width, hidden, "out of it")):
        lhs = jax.random.normal(keys[1], (rows, k), dtype)
        w = jax.random.normal(keys[2], (held, k, cols), dtype) / k ** 0.5
        d = jax.random.normal(keys[3], (rows, cols), dtype)

        def record(what, form, ms, **more):
            line = dict(cell=name, what="grouped %s, %s" % (what, way),
                        form=form, rows=rows, pairs=n,
                        fullest_over_mean=round(float(
                            sizes.max() / jnp.maximum(sizes.mean(), 1)), 2),
                        ms=round(ms, 4), peak_share=round(
                            2 * n * k * cols / (ms * 1e-3) / PEAK_FLOPS, 4),
                        **more)
            lines.append(line)
            print(json.dumps(line), flush=True)

        def ragged(lhs, w):
            return lax.ragged_dot(lhs, w, sizes, preferred_element_type=dtype)
        transposes = lambda lhs, w, d: jax.vjp(ragged, lhs, w)[1](d)
        want = {}
        for what, fn, args in (
                ("product", ragged, (lhs, w)),
                ("dlhs", lambda *a: transposes(*a)[0], (lhs, w, d)),
                ("drhs", lambda *a: transposes(*a)[1], (lhs, w, d))):
            ms, want[what] = device_ms(fn, *args)
            record(what, "ragged_dot", ms)
        for tile in tiles:
            walk = pallas_moe.grouped_walk(sizes, rows, tile)
            fill = float(pallas_moe.grouped_tile_fill(sizes, rows, tile))
            for what, fn, args in (
                    ("product", pallas_moe.grouped_rows, (lhs, w)),
                    ("dlhs", pallas_moe.grouped_rows_t, (d, w)),
                    ("drhs", pallas_moe.grouped_weights, (lhs, d))):
                ms, got = device_ms(functools.partial(
                    fn, tile=tile, interpret=interpret), *args, walk)
                read = held if what == "drhs" else n
                gap = float(jnp.abs(
                    got[:read].astype(jnp.float32)
                    - want[what][:read].astype(jnp.float32)).max()
                    / (jnp.abs(want[what][:read].astype(jnp.float32)).max()
                       + 1e-30))
                record(what, "kernel", ms, tile=tile, fill=round(fill, 4),
                       gap=gap, steps=int(walk.steps[0]))


def profiled(name, form, step, args, iters=5):
    """Every operation of ``step`` by its self time on the device, ms a
    call, into ``chiprun_out/moe_layer_<name>_<form>.txt``."""
    by_name = {}
    for ev, own in device_events(step, args, iters)[0]:
        key = (ev["name"], ev.get("op_name", "")[-90:])
        by_name[key] = by_name.get(key, 0.0) + own / iters / 1e6
    os.makedirs("chiprun_out", exist_ok=True)
    tag = form.replace(" ", "_").replace(",", "")
    with open("chiprun_out/moe_layer_%s_%s.txt" % (name, tag), "w") as f:
        f.write("total %.3f ms a call\n" % sum(by_name.values()))
        for (op, path), ms in sorted(by_name.items(), key=lambda kv: -kv[1]):
            f.write("%8.3f  %-40s %s\n" % (ms, op, path))
    return sum(by_name.values())


def layer(name, shapes, dtype, lines, forms):
    """One layer forward and backward, every name kept across remat."""
    tokens, top_k, experts, held, hidden, width = shapes
    keys = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(keys[0], (tokens, hidden), dtype)
    router = jax.random.normal(keys[1], (hidden, experts)) / hidden ** 0.5
    stack = lambda k, i, o: jax.random.normal(k, (held, i, o)) / i ** 0.5
    weights = (stack(keys[2], hidden, width), stack(keys[3], hidden, width),
               stack(keys[4], width, hidden))
    got = {}
    for form in forms:
        def loss(x, router, *weights, form=form):
            y, _ = jax.checkpoint(
                lambda *a: moe.routed_experts(
                    *a, first_expert=0, top_k=top_k,
                    router=moe.softmax_top_k, kernels=form == "kernel"),
                policy=jax.checkpoint_policies.save_only_these_names(
                    moe.CHOICE_NAME, moe.ROWS_NAME, moe.EXPERT_GATE_UP_NAME))(
                        x, router, None, *weights)
            return (y.astype(jnp.float32) ** 2).mean()
        step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)))
        ms, got[form] = timed(step, x, router, *weights)
        line = dict(cell=name, what="one layer, forward and backward",
                    form=form, ms=round(ms, 4))
        if jax.devices()[0].platform == "tpu":
            line["device_ms"] = round(profiled(
                name, form, step, (x, router, *weights)), 4)
        lines.append(line)
        print(json.dumps(line), flush=True)
    if len(got) >= 2:
        (va, ga), (vb, gb) = list(got.values())[:2]
        worst = max(float(jnp.abs(p.astype(jnp.float32)
                                  - q.astype(jnp.float32)).max()
                          / (jnp.abs(q.astype(jnp.float32)).max() + 1e-30))
                    for p, q in zip(ga, gb))
        line = dict(cell=name, what="kernel against xla, one layer",
                    loss=[float(va), float(vb)], worst_gradient_gap=worst,
                    finite=all(bool(jnp.isfinite(g).all()) for g in ga + gb))
        lines.append(line)
        print(json.dumps(line), flush=True)


def ragged_dot_past_the_groups(lines):
    """Does the grouped product, and its two transposes, read a row
    past the groups' ends?  nan there, and the results say."""
    rows, hidden, width, held = 4096, 2048, 512, 4
    sizes = jnp.array([300, 0, 700, 123], jnp.int32)
    n = int(sizes.sum())
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    lhs = jax.random.normal(keys[0], (rows, hidden), jnp.bfloat16)
    rhs = jax.random.normal(keys[1], (held, hidden, width), jnp.bfloat16)
    d = jax.random.normal(keys[2], (rows, width), jnp.bfloat16)
    spoil = lambda v: v.at[n:].set(jnp.nan)

    def grads(lhs, d):
        product = lambda lhs, rhs: lax.ragged_dot(
            lhs, rhs, sizes, preferred_element_type=jnp.bfloat16)
        out, transpose = jax.vjp(product, lhs, rhs)
        return (out[:n],) + tuple(
            g[:n] if g.shape[0] == rows else g for g in transpose(d))
    clean = jax.jit(grads)(lhs, d)
    spoiled = jax.jit(grads)(spoil(lhs), spoil(d))
    line = dict(what="ragged_dot with nan past the groups' ends",
                finite=[bool(jnp.isfinite(g).all()) for g in spoiled],
                same=[bool((p == q).all()) for p, q in zip(clean, spoiled)])
    lines.append(line)
    print(json.dumps(line), flush=True)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--tiny", action="store_true")
    parser.add_argument("--only", default="")
    parser.add_argument("--layers-only", action="store_true")
    parser.add_argument("--grouped-only", action="store_true")
    parser.add_argument("--tiles", default="128",
                        help="row tiles of the grouped kernels, with commas")
    args = parser.parse_args()
    tiles = [int(t) for t in args.tiles.split(",")]
    if not args.tiny and jax.devices()[0].platform != "tpu":
        sys.exit("moe_pass_bench times the chip; --tiny rehearses off it")
    lines = [dict(device=jax.devices()[0].device_kind)]
    cells = TINY if args.tiny else CELLS
    dtype = jnp.bfloat16
    if args.tiny:
        for kernel in ("pack_rows", "add_rows", "rows_of_tokens",
                       "tokens_of_rows", "gated", "gated_bwd",
                       "grouped_rows", "grouped_rows_t", "grouped_weights"):
            setattr(pallas_moe, kernel, functools.partial(
                getattr(pallas_moe, kernel), interpret=True))
        moe.on_one_tpu = lambda mesh: True
        for name, shapes in cells.items():
            passes(name, shapes, dtype, True, lines)
            grouped(name, shapes, dtype, [16, 32], True, lines)
            layer(name, shapes, dtype, lines, FORMS)
    else:
        ragged_dot_past_the_groups(lines)
        for name, shapes in {**cells, **DEPLOYED}.items():
            if args.only and args.only != name:
                continue
            if not (args.layers_only or args.grouped_only
                    or name in DEPLOYED):
                passes(name, shapes, dtype, False, lines)
            if not args.layers_only:
                grouped(name, shapes, dtype, tiles, False, lines)
            if not (args.grouped_only or name in DEPLOYED):
                layer(name, shapes, dtype, lines, FORMS)
    os.makedirs("chiprun_out", exist_ok=True)
    name = "moe_pass_bench_tiny.json" if args.tiny else "moe_pass_bench.json"
    with open(os.path.join("chiprun_out", name), "w") as f:
        json.dump(lines, f, indent=1)


if __name__ == "__main__":
    main()
