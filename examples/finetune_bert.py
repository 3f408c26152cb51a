"""Fine-tune a BERT classifier with the high-level Model API.

    PYTHONPATH=. python examples/finetune_bert.py --epochs 3
"""

import argparse

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--batch", type=int, default=8)
    args = ap.parse_args()

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu import Model
    from paddle_tpu.io import TensorDataset
    from paddle_tpu.jit import enable_compile_cache
    from paddle_tpu.metric import Accuracy
    from paddle_tpu.models.bert import BertConfig, BertForSequenceClassification
    from paddle_tpu.optimizer import AdamW
    enable_compile_cache()

    cfg = BertConfig(vocab_size=1000, hidden_size=64, num_hidden_layers=2,
                     num_attention_heads=4, intermediate_size=128,
                     max_position_embeddings=64)
    net = BertForSequenceClassification(cfg, num_classes=2)

    # synthetic task: class = whether token 0 is in the upper vocab half
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (256, 32)).astype("int64")
    labels = (ids[:, 0] >= cfg.vocab_size // 2).astype("int64")
    train = TensorDataset([ids[:192], labels[:192]])
    val = TensorDataset([ids[192:], labels[192:]])

    class Net(nn.Layer):
        def __init__(self, bert):
            super().__init__()
            self.bert = bert

        def forward(self, x):
            return self.bert(x)

    model = Model(Net(net))
    model.prepare(
        optimizer=AdamW(learning_rate=3e-4, parameters=net.parameters()),
        loss=nn.CrossEntropyLoss(),
        metrics=Accuracy())
    model.fit(train, val, batch_size=args.batch, epochs=args.epochs,
              verbose=1, shuffle=True)
    print("eval:", model.evaluate(val, batch_size=args.batch, verbose=0))


if __name__ == "__main__":
    main()
