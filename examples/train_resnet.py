"""Train ResNet-18 on synthetic images through the PERF LAYER
(docs/PERFORMANCE.md): channels-last layout pass + fused donation-aware
train step + device-prefetched DataLoader, with bf16 AMP.

    PYTHONPATH=. python examples/train_resnet.py --steps 10
    PYTHONPATH=. python examples/train_resnet.py --steps 10 --nchw  # pass off
"""

import argparse
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--image", type=int, default=64)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--nchw", action="store_true",
                    help="skip the NHWC layout pass (compare layouts)")
    args = ap.parse_args()

    import paddle_tpu.nn as nn
    from paddle_tpu.io import DataLoader
    from paddle_tpu.jit import enable_compile_cache, make_train_step
    from paddle_tpu.optimizer import Momentum
    from paddle_tpu.vision.datasets import FakeImageDataset
    from paddle_tpu.vision.models import resnet18
    enable_compile_cache()

    net = resnet18(num_classes=100)
    if not args.nchw:
        net = nn.ChannelsLast(net)  # TPU-native conv layout, NCHW contract
    opt = Momentum(learning_rate=0.1, momentum=0.9,
                   parameters=net.parameters())
    # fwd + loss + bwd + momentum update as ONE donated XLA program; the
    # DataLoader's buffered reader keeps H2D transfers in flight under it
    train_step = make_train_step(net, opt, nn.CrossEntropyLoss(), amp=True)
    data = DataLoader(
        FakeImageDataset(args.steps * args.batch * 2,
                         (3, args.image, args.image), 100),
        batch_size=args.batch, num_workers=args.workers,
        use_shared_memory=True)

    t0 = time.time()
    for step, (x, y) in enumerate(data):
        if step >= args.steps:
            break
        loss = train_step(x, y)
        print(f"step {step:3d}  loss {float(loss):.4f}")
    print(f"done in {time.time() - t0:.1f}s "
          f"(first two steps include eager warmup + compile)")


if __name__ == "__main__":
    main()
