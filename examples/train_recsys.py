"""Recsys training with the parameter-server equivalent: a wide-vocab
sparse embedding (SelectedRows gradients, host-resident table) + dense MLP
tower (SURVEY §2.5 Parameter server; the reference's
paddle.static.nn.sparse_embedding + a_sync DistributedStrategy workload).

Run:  PYTHONPATH=. python examples/train_recsys.py
Multi-process (vocab-sharded; several processes per node is the CPU
simulation, which the launcher runs only where JAX_PLATFORMS=cpu is set):
      JAX_PLATFORMS=cpu PYTHONPATH=. python -m paddle_tpu.distributed.launch \
          --nproc_per_node 2 examples/train_recsys.py

What it demonstrates:
  * the [vocab, dim] table never hits device HBM (host=True) — the
    per-device embedding-bytes proof is printed each run;
  * backward produces a [batch*slots, dim] SelectedRows gradient, never
    the dense [vocab, dim] one;
  * SparseAdam advances optimizer state only for the touched rows;
  * AsyncLookup overlaps the next batch's host row-gather with the
    current step.
"""

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.distributed.ps import (AsyncLookup, SparseAdam,
                                       SparseEmbedding)
from paddle_tpu.jit import enable_compile_cache

VOCAB = 1_000_000          # 1M ids x 32 dims = 128 MB fp32 — host-resident
DIM = 32
SLOTS = 8                  # feature slots per example
BATCH = 256
STEPS = 20


def main():
    enable_compile_cache()
    rng = np.random.default_rng(0)
    emb = SparseEmbedding(VOCAB, DIM, host=True, seed=1)
    tower = nn.Sequential(nn.Linear(SLOTS * DIM, 64), nn.ReLU(),
                          nn.Linear(64, 1))
    opt_dense = paddle.optimizer.Adam(learning_rate=1e-3,
                                      parameters=tower.parameters())
    opt_sparse = SparseAdam(emb, learning_rate=1e-2)
    prefetch = AsyncLookup(emb)

    table_mb = emb.weight.nbytes / 2 ** 20
    print(f"table: {VOCAB} x {DIM} = {table_mb:.0f} MB host RAM; "
          f"device-resident embedding bytes: {emb.device_bytes()}")

    def batch():
        ids = rng.integers(0, VOCAB, (BATCH, SLOTS)).astype(np.int64)
        # synthetic CTR-ish label from a fixed hash of the ids
        y = ((ids.sum(1) % 97) / 96.0).astype(np.float32)[:, None]
        return ids, y

    ids_np, y_np = batch()
    for step in range(STEPS):
        ids_next, y_next = batch()
        prefetch.prefetch(ids_next)     # next batch's host gather overlaps
        out = emb(paddle.to_tensor(ids_np))            # gathers hot rows
        flat = paddle.reshape(out, [BATCH, SLOTS * DIM])
        pred = tower(flat)
        loss = ((pred - paddle.to_tensor(y_np)) ** 2).mean()
        loss.backward()

        sel = emb.sparse_grad()
        opt_sparse.step(sel)                           # touches O(batch) rows
        opt_dense.step()
        opt_dense.clear_grad()
        if step % 5 == 0 or step == STEPS - 1:
            print(f"step {step:3d} loss {float(loss.numpy()):.5f} "
                  f"sparse-grad rows {sel.merge().ids.shape[0]} "
                  f"(of {VOCAB})")
        prefetch.take()                 # join the overlap for step t+1
        ids_np, y_np = ids_next, y_next

    print("done: dense [vocab, dim] gradients were never materialized; "
          f"device embedding bytes stayed {emb.device_bytes()}")


if __name__ == "__main__":
    main()
