"""Synthetic-data throughput benchmark for the torch binding.

Analog of the reference's north-star harness
(reference examples/pytorch_synthetic_benchmark.py:14-107): fixed fake
data, a timed ``benchmark_step`` of forward/backward/optimizer-step under
``DistributedOptimizer``, warmup + per-iteration img/sec with a mean ±
stddev summary and the total across workers.  Differences from the
reference are TPU-environment facts, not protocol changes:

* torchvision is not bundled, so the default model is a small in-file
  convnet (``--model convnet|mlp``, widths via ``--hidden``); the
  protocol (warmup/batches-per-iter/iters, img/sec accounting) is the
  reference's.
* torch here is CPU-only and the binding's allreduce is the EAGER
  host-staged path (numpy views → device/TCP data plane) — this harness
  exists precisely to record what that path delivers.  Throughput-
  critical training belongs on the compiled jax path
  (docs/troubleshooting.md steers migrators there).

Run single-process, or under the launcher like the reference under
mpirun:

    python examples/torch_synthetic_benchmark.py
    python -m horovod_tpu.run -np 2 python examples/torch_synthetic_benchmark.py
"""

from __future__ import annotations

import argparse
import timeit

import numpy as np
import torch
import torch.nn.functional as F

import horovod_tpu.torch as hvd


class ConvNet(torch.nn.Module):
    """Small image model: enough conv/linear mix that gradients span many
    shapes (the fusion-relevant case), small enough for CPU timing."""

    def __init__(self, hidden: int = 64):
        super().__init__()
        self.conv1 = torch.nn.Conv2d(3, hidden, 3, stride=2, padding=1)
        self.conv2 = torch.nn.Conv2d(hidden, hidden, 3, stride=2, padding=1)
        self.conv3 = torch.nn.Conv2d(hidden, hidden, 3, stride=2, padding=1)
        self.fc1 = torch.nn.Linear(hidden * 4 * 4, 512)
        self.fc2 = torch.nn.Linear(512, 1000)

    def forward(self, x):
        x = F.relu(self.conv1(x))
        x = F.relu(self.conv2(x))
        x = F.relu(F.adaptive_avg_pool2d(self.conv3(x), 4))
        x = x.flatten(1)
        return self.fc2(F.relu(self.fc1(x)))


class MLP(torch.nn.Module):
    def __init__(self, hidden: int = 1024):
        super().__init__()
        self.net = torch.nn.Sequential(
            torch.nn.Flatten(),
            torch.nn.Linear(3 * 32 * 32, hidden), torch.nn.ReLU(),
            torch.nn.Linear(hidden, hidden), torch.nn.ReLU(),
            torch.nn.Linear(hidden, 1000))

    def forward(self, x):
        return self.net(x)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", choices=["convnet", "mlp"], default="convnet")
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--image-size", type=int, default=32,
                    help="input H=W (CPU-budget default; the reference "
                    "used 224 on GPUs)")
    ap.add_argument("--num-warmup-batches", type=int, default=4)
    ap.add_argument("--num-batches-per-iter", type=int, default=4)
    ap.add_argument("--num-iters", type=int, default=8)
    ap.add_argument("--fp16-allreduce", action="store_true",
                    help="bf16-compressed wire (reference --fp16-allreduce)")
    args = ap.parse_args()

    hvd.init()
    torch.manual_seed(42)
    torch.set_num_threads(max(torch.get_num_threads() // hvd.size(), 1))

    model = (ConvNet(args.hidden) if args.model == "convnet"
             else MLP(args.hidden))
    optimizer = torch.optim.SGD(model.parameters(), lr=0.01)
    compression = (hvd.Compression.bf16 if args.fp16_allreduce
                   else hvd.Compression.none)
    optimizer = hvd.DistributedOptimizer(
        optimizer, named_parameters=model.named_parameters(),
        compression=compression)
    hvd.broadcast_parameters(model.state_dict(), root_rank=0)
    hvd.broadcast_optimizer_state(optimizer, root_rank=0)

    data = torch.randn(args.batch_size, 3, args.image_size, args.image_size)
    target = torch.LongTensor(args.batch_size).random_() % 1000

    def benchmark_step():
        optimizer.zero_grad()
        loss = F.cross_entropy(model(data), target)
        loss.backward()
        optimizer.step()

    def log(s):
        if hvd.rank() == 0:
            print(s)

    nparam = sum(p.numel() for p in model.parameters())
    log(f"Model: {args.model} ({nparam / 1e6:.1f}M params)")
    log(f"Batch size: {args.batch_size}  (image {args.image_size}px)")
    log(f"Number of workers: {hvd.size()}")

    log("Running warmup...")
    timeit.timeit(benchmark_step, number=args.num_warmup_batches)

    log("Running benchmark...")
    img_secs = []
    for x in range(args.num_iters):
        t = timeit.timeit(benchmark_step, number=args.num_batches_per_iter)
        img_sec = args.batch_size * args.num_batches_per_iter / t
        log(f"Iter #{x}: {img_sec:.1f} img/sec per worker")
        img_secs.append(img_sec)

    img_sec_mean = np.mean(img_secs)
    img_sec_conf = 1.96 * np.std(img_secs)
    log(f"Img/sec per worker: {img_sec_mean:.1f} +-{img_sec_conf:.1f}")
    log(f"Total img/sec on {hvd.size()} worker(s): "
        f"{hvd.size() * img_sec_mean:.1f} +-{hvd.size() * img_sec_conf:.1f}")


if __name__ == "__main__":
    main()
