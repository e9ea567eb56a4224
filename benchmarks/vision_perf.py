"""Vision north-star benchmark: ResNet-50 training img/s on the chip.

BASELINE.md north-star row 2: "JaxTrainer ResNet-50/CIFAR-10 (single-host
DP) img/s vs GPU table" — the reference's GPU image-training table
(doc/source/ray-air/benchmarks.rst:158-174) measures a torch trainer at
40.7 img/s on 1 GPU and 746.3 img/s on 16 GPUs (224px images).  Two rows
here, both through the repo's sharded vision train step
(train/step.py make_vision_train — the same step JaxTrainer workers run):

  - resnet50_cifar10:        32px/10-class, the north-star config.
  - resnet50_imagenet_shape: 224px/1000-class synthetic, the row directly
                             comparable to the reference's GPU table.

  python benchmarks/vision_perf.py [--steps 30] [--batch 256]

Prints one JSON line per row.
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def bench(image_px: int, num_classes: int, batch: int, steps: int,
          warmup: int, label: str, reference: str):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.resnet import ResNet50
    from ray_tpu.parallel import MeshConfig, build_mesh
    from ray_tpu.train.step import OptimizerConfig, make_vision_train

    mesh = build_mesh(MeshConfig(data=-1))
    model = ResNet50(num_classes=num_classes, small_inputs=image_px <= 64)
    rng = np.random.default_rng(0)
    batch_data = {
        "image": jnp.asarray(rng.standard_normal(
            (batch, image_px, image_px, 3)), jnp.float32),
        "label": jnp.asarray(rng.integers(0, num_classes, (batch,)),
                             jnp.int32),
    }
    init_fn, step_fn, _, _ = make_vision_train(
        model, mesh, OptimizerConfig(warmup_steps=10, decay_steps=1000),
        example_batch=batch_data)
    state = init_fn(jax.random.PRNGKey(0), batch_data)
    for _ in range(warmup):
        state, metrics = step_fn(state, batch_data)
    jax.block_until_ready((state, metrics))
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step_fn(state, batch_data)
    final_loss = float(metrics["loss"])
    dt = (time.perf_counter() - t0) / steps
    dev = jax.devices()[0]
    return {
        "metric": f"vision_{label}",
        "model": "resnet50",
        "image_px": image_px,
        "num_classes": num_classes,
        "batch": batch,
        "img_per_s": round(batch / dt, 1),
        "step_ms": round(dt * 1e3, 2),
        "final_loss": round(final_loss, 4),
        "device": getattr(dev, "device_kind", dev.platform),
        "n_chips": mesh.size,
        "reference": reference,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=None)
    args = ap.parse_args()

    import jax
    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        rows = [
            bench(32, 10, args.batch or 1024, args.steps, 3,
                  "resnet50_cifar10",
                  "north-star config (BASELINE.md row 2); 32px has no "
                  "direct reference number"),
            bench(224, 1000, args.batch or 256, args.steps, 3,
                  "resnet50_imagenet_shape",
                  "reference GPU table benchmarks.rst:166: 40.7 img/s "
                  "on 1 GPU (g4dn, torch), 746.3 img/s on 16 GPUs; this "
                  "row is synthetic device-resident data (no input "
                  "pipeline), pure train-step throughput"),
        ]
    else:   # CI smoke: tiny shapes, throughput not meaningful
        rows = [bench(32, 10, args.batch or 16, 3, 1,
                      "resnet50_cifar10_smoke", "cpu smoke")]
    for row in rows:
        print(json.dumps(row))
        sys.stdout.flush()


if __name__ == "__main__":
    main()
