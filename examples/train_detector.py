"""Train the PP-YOLOE-style detector (MobileNetV3 + FPN + decoupled head)
on synthetic boxes, then run static-shape NMS inference.

    PYTHONPATH=. python examples/train_detector.py --steps 5 --image 64
"""

import argparse

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--image", type=int, default=64)
    ap.add_argument("--classes", type=int, default=3)
    args = ap.parse_args()

    import paddle_tpu as paddle
    from paddle_tpu.jit import enable_compile_cache
    from paddle_tpu.optimizer import Adam
    from paddle_tpu.vision.detection import (detection_loss, ppyoloe_mbv3,
                                             static_nms)
    enable_compile_cache()

    paddle.seed(0)
    det = ppyoloe_mbv3(num_classes=args.classes, image_size=args.image)
    opt = Adam(learning_rate=3e-4, parameters=det.parameters())
    pts, strides = det.anchor_points()

    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal(
        (2, 3, args.image, args.image)).astype(np.float32))
    gt_b = paddle.to_tensor(np.asarray(
        [[[8, 8, 40, 40]], [[20, 20, 60, 60]]], np.float32))
    gt_l = paddle.to_tensor(np.asarray([[1], [0]], np.int32))

    for step in range(args.steps):
        cls, boxes = det(x)
        loss = detection_loss(cls, boxes, gt_b, gt_l, pts, strides,
                              args.classes)
        loss.backward()
        opt.step()
        opt.clear_grad()
        print(f"step {step}: loss {float(loss.numpy()):.4f}")

    # inference: per-class static multiclass NMS (the reference's
    # multiclass_nms contract — suppression runs within each class via a
    # vmapped greedy kernel, then one global keep_top_k; all shapes fixed,
    # runs inside jit)
    cls, boxes = det(x)
    import jax.nn
    from paddle_tpu.vision.ops import multiclass_nms
    scores_cm = paddle.to_tensor(
        np.asarray(jax.nn.sigmoid(cls._value)).transpose(0, 2, 1))  # [B,C,A]
    out, idx, count = multiclass_nms(boxes, scores_cm,
                                     score_threshold=0.05,
                                     nms_top_k=32, keep_top_k=8,
                                     nms_threshold=0.6)
    n = int(count.numpy()[0])
    print("detections kept:", n, "of", out.shape[1])
    det_rows = out.numpy()[0][:max(n, 1)]
    print("top (label, score, box):")
    for row in det_rows[:3]:
        print(f"  class {int(row[0])} score {row[1]:.3f} "
              f"box {row[2:].round(1)}")


if __name__ == "__main__":
    main()
