"""ResNet-50 ImageNet training — the flagship benchmark config.

Reference: zoo/.../examples/resnet/TrainImageNet.scala:36-120 (warmup +
epoch-decay SGD) and the vnni Perf harness
(examples/vnni/bigdl/Perf.scala:53-66) that prints images/sec.

With --data-dir it trains on ``.npz`` image shards (uint8 HWC
images + int labels); without, synthetic data measures training throughput.

The input pipeline is TPU-shaped: the host ships **uint8** images (4× less
host→device traffic than f32) and normalization runs on-device inside the
compiled step (``FeatureSet.transform_on_device``).  ``run`` measures and
reports separately:

- ``pure_step``: the jitted train step on a device-resident batch — the
  framework's compute number;
- ``e2e``: end-to-end ``fit`` including host batch assembly + H2D infeed;
- ``infeed_fraction``: (e2e − pure) / e2e — how much of the wall clock the
  infeed fails to hide behind compute;
- ``compiles_timed``: XLA compilations observed during the timed epoch
  (must be 0 — anything else means per-step retracing).

Usage:
    python examples/resnet/train_imagenet.py --steps 30 --batch-size 256
"""

import argparse
import logging
import time

import numpy as np

# ImageNet channel stats (uint8 scale), applied on device.
_MEAN = np.array([123.675, 116.28, 103.53], np.float32)
_STD = np.array([58.395, 57.12, 57.375], np.float32)


class _CompileCounter(logging.Handler):
    """Counts XLA compile events (jax_log_compiles messages)."""

    def __init__(self):
        super().__init__()
        self.count = 0

    def emit(self, record):
        # jax_log_compiles emits both "Compiling <fn>..." (pxla) and
        # "Finished tracing + compilation..." (dispatch) per compile; count
        # only the former so the magnitude is exact.
        if record.getMessage().startswith("Compiling"):
            self.count += 1


def _normalize(batch):
    import jax.numpy as jnp

    x = batch["x"].astype(jnp.float32)
    x = (x - jnp.asarray(_MEAN)) / jnp.asarray(_STD)
    return {**batch, "x": x}


def run(image_size=224, per_chip_batch=256, steps=30, classes=1000,
        depth=50, data_dir=None, warmup_batches=2):
    """Train ResNet-`depth` for `steps` steps; returns a result dict."""
    import jax

    from analytics_zoo_tpu import init_zoo_context
    from analytics_zoo_tpu.feature.dataset import FeatureSet
    from analytics_zoo_tpu.models.resnet import ResNet

    ctx = init_zoo_context("resnet imagenet")
    model = ResNet.image_net(depth, classes=classes,
                             input_shape=(image_size, image_size, 3))
    model.compile(
        optimizer=ResNet.imagenet_optimizer(batch_size=per_chip_batch,
                                            steps_per_epoch=5004),
        loss="sparse_categorical_crossentropy",
    )
    batch = per_chip_batch * max(ctx.data_parallel_size, 1)

    if data_dir:
        from analytics_zoo_tpu.feature.imagenet import imagenet_feature_set

        train_set = imagenet_feature_set(data_dir, image_size)
    else:
        n = batch * steps
        rng = np.random.default_rng(0)
        x = rng.integers(0, 256, size=(n, image_size, image_size, 3),
                         dtype=np.uint8)
        y = rng.integers(0, classes, size=(n,)).astype(np.int32)
        train_set = FeatureSet.of(x, y)
    train_set.transform_on_device(_normalize)
    n = train_set.num_samples // batch * batch
    steps_run = n // batch
    if steps_run < 1:
        raise ValueError(
            f"dataset has {train_set.num_samples} samples — fewer than one "
            f"global batch ({batch}); reduce --batch-size or add data")

    # Bounded warmup (compile + first dispatches), never a full --data-dir
    # epoch: a tiny synthetic set with the same shapes compiles the same
    # XLA program.
    wrng = np.random.default_rng(1)
    warm = FeatureSet.of(
        wrng.integers(0, 256, size=(batch * warmup_batches, image_size,
                                    image_size, 3), dtype=np.uint8),
        wrng.integers(0, classes,
                      size=(batch * warmup_batches,)).astype(np.int32),
    ).transform_on_device(_normalize)
    model.fit(warm, batch_size=batch, nb_epoch=1)

    # Timed end-to-end epoch, counting any (unexpected) recompiles.
    jax.config.update("jax_log_compiles", True)
    counter = _CompileCounter()
    logging.getLogger("jax").addHandler(counter)
    try:
        t0 = time.perf_counter()
        model.fit(train_set, batch_size=batch, nb_epoch=1)
        e2e_dt = time.perf_counter() - t0
    finally:
        jax.config.update("jax_log_compiles", False)
        logging.getLogger("jax").removeHandler(counter)

    # Pure-device step: same compiled fn on a device-resident batch
    # (fresh buffers inside the hook, so donation can't touch live state).
    # Multi-host: this host materializes only its rows, like fit() does.
    ps = ((jax.process_index(), jax.process_count())
          if jax.process_count() > 1 else None)
    first = next(iter(train_set.batches(batch, shuffle=False, epoch=0,
                                        process_shard=ps)))
    pure_dt = model._estimator.measure_pure_step(
        first, n_steps=min(20, steps_run),
        device_transform=train_set.device_transform)

    e2e_ips = n / e2e_dt
    pure_ips = batch / pure_dt
    return {
        "ctx": ctx,
        "e2e_ips": e2e_ips,
        "pure_ips": pure_ips,
        "pure_step_ms": pure_dt * 1e3,
        "infeed_fraction": max(0.0, 1.0 - (pure_dt * steps_run) / e2e_dt),
        "compiles_timed": counter.count,
        "steps_timed": steps_run,
        "batch": batch,
        "image_size": image_size,
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--data-dir", default=None,
                    help="dir of .npz shards (default: synthetic)")
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--batch-size", type=int, default=256,
                    help="per-chip batch size")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--depth", type=int, default=50)
    args = ap.parse_args()

    r = run(image_size=args.image_size, per_chip_batch=args.batch_size,
            steps=args.steps, depth=args.depth, data_dir=args.data_dir)
    ctx = r["ctx"]
    dp = max(ctx.data_parallel_size, 1)
    print(f"e2e: {r['e2e_ips']:.1f} img/s ({r['e2e_ips'] / dp:.1f}/chip) | "
          f"pure step: {r['pure_ips']:.1f} img/s "
          f"({r['pure_step_ms']:.1f} ms) | "
          f"infeed fraction: {r['infeed_fraction']:.2f} | "
          f"compiles during timing: {r['compiles_timed']} | "
          f"{ctx.num_devices} {ctx.platform} device(s)")


if __name__ == "__main__":
    import os
    import sys

    # allow `python examples/<domain>/<script>.py` from anywhere: put the
    # repo root (two levels up) on sys.path before importing the package
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    main()
