"""MNIST with the torch binding.

Analog of reference examples/pytorch_mnist.py: same model (:30-45), LR scaled
by size, DistributedOptimizer with gradient hooks, broadcast of parameters
and optimizer state before training (:77-80), per-process data sharding.
With ``--ckpt-dir`` it also exercises the reference's checkpoint/resume
contract (examples/pytorch_imagenet_resnet50.py:63-72): rank 0 writes
torch state per epoch, and on restart every rank agrees on the resume
epoch via broadcast before rank 0's weights are re-broadcast.
"""

from __future__ import annotations

import argparse
import os

import numpy as np
import torch
import torch.nn.functional as F

import horovod_tpu.torch as hvd


class Net(torch.nn.Module):
    """Reference pytorch_mnist.py:30-45 architecture."""

    def __init__(self):
        super().__init__()
        self.conv1 = torch.nn.Conv2d(1, 10, kernel_size=5)
        self.conv2 = torch.nn.Conv2d(10, 20, kernel_size=5)
        self.conv2_drop = torch.nn.Dropout2d()
        self.fc1 = torch.nn.Linear(320, 50)
        self.fc2 = torch.nn.Linear(50, 10)

    def forward(self, x):
        x = F.relu(F.max_pool2d(self.conv1(x), 2))
        x = F.relu(F.max_pool2d(self.conv2_drop(self.conv2(x)), 2))
        x = x.view(-1, 320)
        x = F.relu(self.fc1(x))
        x = F.dropout(x, training=self.training)
        return F.log_softmax(self.fc2(x), dim=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--ckpt-dir", default=None,
                    help="enable per-epoch checkpoint + resume")
    args = ap.parse_args()

    hvd.init()
    torch.manual_seed(42 + hvd.rank())

    model = Net()
    # Horovod: scale LR by size; wrap optimizer; broadcast state
    # (reference :69-80).
    optimizer = torch.optim.SGD(model.parameters(),
                                lr=args.lr * hvd.size(), momentum=0.5)
    optimizer = hvd.DistributedOptimizer(
        optimizer, named_parameters=model.named_parameters())

    # Resume: rank 0 reads the filesystem, the epoch number travels by
    # broadcast so stale-FS workers agree (reference
    # pytorch_imagenet_resnet50.py:63-72), then weights broadcast below.
    resume_epoch = -1
    if args.ckpt_dir:
        if hvd.rank() == 0 and os.path.isdir(args.ckpt_dir):
            for entry in os.listdir(args.ckpt_dir):
                if entry.startswith("epoch_"):
                    try:
                        resume_epoch = max(resume_epoch,
                                           int(entry.split("_", 1)[1]))
                    except ValueError:
                        pass  # stray/partial files don't break startup
        resume_epoch = hvd.broadcast_object(resume_epoch, root_rank=0)
        if resume_epoch >= 0 and hvd.rank() == 0:
            ck = torch.load(os.path.join(args.ckpt_dir,
                                         f"epoch_{resume_epoch}"),
                            weights_only=True)
            model.load_state_dict(ck["model"])
            optimizer.load_state_dict(ck["optimizer"])
            print(f"resumed from epoch {resume_epoch}")

    hvd.broadcast_parameters(model.state_dict(), root_rank=0)
    # Momentum buffers must resume too or the trajectory diverges from an
    # uninterrupted run (reference broadcast_optimizer_state after load).
    hvd.broadcast_optimizer_state(optimizer, root_rank=0)

    # Synthetic MNIST-shaped data, sharded by rank (DistributedSampler
    # analog, reference :50-56).
    rng = np.random.RandomState(0)
    x = torch.tensor(rng.rand(2048, 1, 28, 28), dtype=torch.float32)
    y = torch.tensor((rng.rand(2048) * 10).astype(np.int64))
    x, y = x[hvd.rank()::hvd.size()], y[hvd.rank()::hvd.size()]

    model.train()
    for epoch in range(resume_epoch + 1, args.epochs):
        perm = torch.randperm(len(x))
        loss = None
        for lo in range(0, len(x) - args.batch_size, args.batch_size):
            idx = perm[lo:lo + args.batch_size]
            optimizer.zero_grad()
            loss = F.nll_loss(model(x[idx]), y[idx])
            loss.backward()
            optimizer.step()
        if args.ckpt_dir and hvd.rank() == 0:
            # Rank-0-only writes (reference README.md:102-104 contract),
            # atomically: a crash mid-save must not leave a truncated file
            # that the resume scan would pick up.
            os.makedirs(args.ckpt_dir, exist_ok=True)
            final = os.path.join(args.ckpt_dir, f"epoch_{epoch}")
            tmp = final + ".tmp"
            torch.save({"model": model.state_dict(),
                        "optimizer": optimizer.state_dict(),
                        "epoch": epoch}, tmp)
            os.replace(tmp, final)
        if hvd.rank() == 0:
            print(f"epoch {epoch}: loss={float(loss):.4f}")

    # Every rank reports the globally-averaged final metric (identical by
    # construction — multi-process CI asserts this, tests/test_examples_frameworks.py).
    final = hvd.allreduce(loss.detach() if loss is not None
                          else torch.zeros(()), average=True)
    print(f"[rank {hvd.rank()}/{hvd.size()}] final loss={float(final):.6f}",
          flush=True)


if __name__ == "__main__":
    main()
