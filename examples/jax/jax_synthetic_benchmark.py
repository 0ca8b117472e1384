"""JAX ResNet-50 synthetic benchmark — the flagship compiled-SPMD path
(reference metric: examples/tensorflow2/tensorflow2_synthetic_benchmark
img/sec = batch_size × num_batches_per_iter / time).

Single process drives all local TPU chips through the mesh; multi-host
via horovodrun adds the DCN dimension.

Run:  python jax_synthetic_benchmark.py --batch-size 64 --num-iters 3
"""

import argparse
import timeit
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models.resnet import ResNet50
from horovod_tpu.parallel import build_mesh, sharded, replicated


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--batch-size", type=int, default=64,
                        help="Global batch size.")
    parser.add_argument("--image-size", type=int, default=224)
    parser.add_argument("--num-warmup-batches", type=int, default=2)
    parser.add_argument("--num-batches-per-iter", type=int, default=5)
    parser.add_argument("--num-iters", type=int, default=3)
    parser.add_argument("--bf16", action="store_true", default=True)
    args = parser.parse_args()

    hvd.init()
    n_dev = jax.local_device_count()
    mesh = build_mesh({"dp": n_dev})
    dtype = jnp.bfloat16 if args.bf16 else jnp.float32

    model = ResNet50(num_classes=1000, dtype=dtype)
    rng = jax.random.PRNGKey(0)
    batch = jnp.zeros((args.batch_size, args.image_size,
                       args.image_size, 3), dtype)
    tx = optax.sgd(0.01, momentum=0.9)

    # One compiled init placed on the mesh; unjitted, flax runs the
    # forward op by op on the first chip.
    @partial(jax.jit, out_shardings=replicated(mesh))
    def init(rng, batch):
        variables = model.init(rng, batch, train=False)
        params = variables["params"]
        return params, variables.get("batch_stats", {}), tx.init(params)

    params, batch_stats, opt_state = init(rng, batch)
    x_sharding = sharded(mesh, "dp")

    @jax.jit
    def train_step(params, batch_stats, opt_state, x, y):
        def loss_fn(p):
            out, new_model_state = model.apply(
                {"params": p, "batch_stats": batch_stats}, x,
                train=True, mutable=["batch_stats"])
            loss = optax.softmax_cross_entropy_with_integer_labels(
                out.astype(jnp.float32), y).mean()
            return loss, new_model_state["batch_stats"]

        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        updates, new_opt = tx.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), new_stats,
                new_opt, loss)

    data = jax.device_put(
        jnp.asarray(np.random.randn(args.batch_size, args.image_size,
                                    args.image_size, 3), dtype),
        x_sharding)
    labels = jax.device_put(
        jnp.asarray(np.random.randint(0, 1000, args.batch_size)),
        x_sharding)

    def benchmark_step():
        nonlocal params, batch_stats, opt_state
        params, batch_stats, opt_state, loss = train_step(
            params, batch_stats, opt_state, data, labels)
        jax.block_until_ready(loss)

    def log(s):
        if hvd.rank() == 0:
            print(s, flush=True)

    log(f"ResNet-50, global batch {args.batch_size}, {n_dev} chips, "
        f"dtype {dtype.__name__}")
    timeit.timeit(benchmark_step, number=args.num_warmup_batches)

    img_secs = []
    for x in range(args.num_iters):
        t = timeit.timeit(benchmark_step,
                          number=args.num_batches_per_iter)
        img_sec = args.batch_size * args.num_batches_per_iter / t
        log(f"Iter #{x}: {img_sec:.1f} img/sec")
        img_secs.append(img_sec)
    log(f"Img/sec: {np.mean(img_secs):.1f} +-{1.96 * np.std(img_secs):.1f}"
        f" ({np.mean(img_secs) / n_dev:.1f}/chip)")


if __name__ == "__main__":
    main()
