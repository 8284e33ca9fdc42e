#!/usr/bin/env python
"""PyTorch synthetic benchmark on the torch binding surface.

Reference parity: `examples/pytorch_synthetic_benchmark.py` — torchvision
ResNet-50, DistributedOptimizer with per-parameter backward-hook
allreduces, warmup + timed rounds, img/sec ± 1.96σ. torch runs on CPU in
this build; the collectives execute on the device mesh through the shared
engine — use this to see the binding/engine overhead; device throughput
is the SPMD path's, measured by ``python3 -m chipbench.run``.

    hvdrun -np 2 python examples/pytorch_synthetic_benchmark.py \
        --model resnet18 --batch-size 8
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="resnet18",
                   help="any torchvision.models constructor name")
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--image-size", type=int, default=64)
    p.add_argument("--num-warmup-batches", type=int, default=2)
    p.add_argument("--num-batches-per-iter", type=int, default=3)
    p.add_argument("--num-iters", type=int, default=3)
    p.add_argument("--fp16-allreduce", action="store_true")
    p.add_argument("--use-adasum", action="store_true")
    args = p.parse_args()

    import torch
    import torch.nn.functional as F

    import horovod_tpu.torch as hvd

    hvd.init()
    torch.manual_seed(42)

    try:
        import torchvision.models as tvm

        model = getattr(tvm, args.model)(num_classes=1000)
    except ImportError:  # torchvision not in the image: tiny fallback net
        model = torch.nn.Sequential(
            torch.nn.Conv2d(3, 16, 3, stride=2), torch.nn.ReLU(),
            torch.nn.AdaptiveAvgPool2d(1), torch.nn.Flatten(),
            torch.nn.Linear(16, 1000))

    lr = 0.01 * hvd.size()
    opt = torch.optim.SGD(model.parameters(), lr=lr, momentum=0.9)
    compression = (hvd.Compression.fp16 if args.fp16_allreduce
                   else hvd.Compression.none)
    opt = hvd.DistributedOptimizer(
        opt, named_parameters=model.named_parameters(),
        compression=compression,
        op=hvd.Adasum if args.use_adasum else hvd.Average)
    hvd.broadcast_parameters(model.state_dict(), root_rank=0)

    data = torch.randn(args.batch_size, 3, args.image_size, args.image_size)
    target = torch.randint(0, 1000, (args.batch_size,))

    def step():
        opt.zero_grad()
        loss = F.cross_entropy(model(data), target)
        loss.backward()
        opt.step()

    for _ in range(args.num_warmup_batches):
        step()

    img_secs = []
    for _ in range(args.num_iters):
        t0 = time.time()
        for _ in range(args.num_batches_per_iter):
            step()
        img_secs.append(args.batch_size * args.num_batches_per_iter /
                        (time.time() - t0))

    img_sec = np.mean(img_secs)
    conf = 1.96 * np.std(img_secs)
    if hvd.rank() == 0:
        print(f"Img/sec per rank: {img_sec:.1f} +- {conf:.1f}")
        print(f"Total img/sec on {hvd.size()} rank(s): "
              f"{hvd.size() * img_sec:.1f} +- {hvd.size() * conf:.1f}")
    hvd.shutdown()


if __name__ == "__main__":
    main()
