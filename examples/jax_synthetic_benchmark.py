"""Synthetic ResNet benchmark — img/sec ± CI, per device and total
(reference: examples/pytorch_synthetic_benchmark.py:1-110,
examples/tensorflow_synthetic_benchmark.py).

Single-process SPMD over all visible devices (the TPU-native shape):
    python examples/jax_synthetic_benchmark.py --batch-size 128
Multi-process via the launcher also works; each process then benches
its own chip and the allreduce rides the negotiated runtime.
"""

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp
import optax

import horovod_tpu.jax as hvd
from horovod_tpu import spmd
from horovod_tpu.models import ResNet50, ResNet101
from horovod_tpu.utils.compile_cache import enable_compile_cache


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="resnet50",
                   choices=["resnet50", "resnet101"])
    p.add_argument("--batch-size", type=int, default=128,
                   help="per-device batch size")
    p.add_argument("--num-warmup-batches", type=int, default=5)
    p.add_argument("--num-batches-per-iter", type=int, default=10)
    p.add_argument("--num-iters", type=int, default=3)
    p.add_argument("--fp16-allreduce", action="store_true",
                   help="(kept for CLI parity; SPMD grads are averaged "
                        "in-graph where XLA picks the wire type)")
    args = p.parse_args()

    enable_compile_cache()
    hvd.init()
    devices = jax.devices()
    n_dev = len(devices)
    mesh = spmd.create_mesh({"data": n_dev})

    model_cls = ResNet50 if args.model == "resnet50" else ResNet101
    model = model_cls(num_classes=1000, dtype=jnp.bfloat16)
    batch = args.batch_size * n_dev

    rng = jax.random.key(0)
    images = jax.device_put(
        jax.random.normal(rng, (batch, 224, 224, 3), jnp.bfloat16),
        spmd.batch_sharding(mesh))
    labels = jax.device_put(jnp.zeros((batch,), jnp.int32),
                            spmd.batch_sharding(mesh))

    variables = jax.jit(lambda r, x: model.init(r, x, train=True))(
        rng, images)
    params, batch_stats = variables["params"], variables["batch_stats"]
    tx = optax.sgd(0.01, momentum=0.9)
    opt_state = tx.init(params)

    def loss_fn(p, bs, x, y):
        logits, upd = model.apply({"params": p, "batch_stats": bs}, x,
                                  train=True, mutable=["batch_stats"])
        oh = jax.nn.one_hot(y, 1000)
        return -jnp.mean(jnp.sum(jax.nn.log_softmax(logits) * oh, -1)), \
            upd["batch_stats"]

    @jax.jit
    def step(p, bs, os_, x, y):
        (l, nbs), g = jax.value_and_grad(loss_fn, has_aux=True)(
            p, bs, x, y)
        u, nos = tx.update(g, os_, p)
        return optax.apply_updates(p, u), nbs, nos, l

    def run_batches(n):
        nonlocal params, batch_stats, opt_state
        for _ in range(n):
            params, batch_stats, opt_state, loss = step(
                params, batch_stats, opt_state, images, labels)
        jax.block_until_ready(loss)

    run_batches(args.num_warmup_batches)
    img_secs = []
    for i in range(args.num_iters):
        t0 = time.perf_counter()
        run_batches(args.num_batches_per_iter)
        dt = time.perf_counter() - t0
        ips = batch * args.num_batches_per_iter / dt
        if hvd.rank() == 0:
            print(f"Iter #{i}: {ips:.1f} img/sec ({n_dev} device(s))")
        img_secs.append(ips)

    if hvd.rank() == 0:
        mean, conf = np.mean(img_secs), 1.96 * np.std(img_secs)
        print(f"Img/sec per device: {mean / n_dev:.1f} "
              f"+-{conf / n_dev:.1f}")
        print(f"Total img/sec on {n_dev} device(s): "
              f"{mean * hvd.size():.1f} +-{conf * hvd.size():.1f}")
    hvd.shutdown()


if __name__ == "__main__":
    main()
