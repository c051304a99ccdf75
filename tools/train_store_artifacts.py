"""Train and register the packaged model-store artifacts.

No-egress substitute for the reference's S3 pretrained corpus
(`python/mxnet/gluon/model_zoo/model_store.py:31`): artifacts are trained
in-repo on the sklearn handwritten-digits set (vision) and a synthetic
char corpus (RNN), then registered into `gluon/model_zoo/_store` with
sha1 checksums so `get_model(..., pretrained=True)` round-trips.

Usage:  python tools/train_store_artifacts.py [--store-dir DIR]
"""
from __future__ import annotations

import argparse
import os
import sys

import numpy as onp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import incubator_mxnet_tpu as mx  # noqa: E402
from incubator_mxnet_tpu import autograd, gluon, np  # noqa: E402


def _digits():
    from sklearn.datasets import load_digits

    d = load_digits()
    X = d.images.astype("float32") / 16.0
    Y = d.target.astype("int32")
    idx = onp.random.RandomState(0).permutation(len(X))
    X, Y = X[idx], Y[idx]
    n_tr = int(0.8 * len(X))
    X = onp.repeat(onp.repeat(X, 4, axis=1), 4, axis=2)   # 8x8 -> 32x32
    X = onp.stack([X] * 3, axis=1)                        # 3 channels
    return (X[:n_tr], Y[:n_tr]), (X[n_tr:], Y[n_tr:])


def train_mobilenet_v2(store_dir):
    from incubator_mxnet_tpu.gluon.model_zoo import model_store
    from incubator_mxnet_tpu.gluon.model_zoo.vision import mobilenet_v2_0_25

    (Xtr, Ytr), (Xte, Yte) = _digits()
    from incubator_mxnet_tpu import optimizer as opt
    from incubator_mxnet_tpu.parallel.sharded import DataParallel

    mx.random.seed(0)
    net = mobilenet_v2_0_25(classes=10)
    net.initialize()
    net(np.array(Xtr[:2]))          # shape inference
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    # compiled train step (ONE program per step instead of ~500 eager
    # op dispatches for this net). MobileNetV2:
    # BN-normalized throughout, trains stably where squeezenet (no norm
    # layers at all) diverges on this input scale.
    dp = DataParallel(net, lambda out, y: loss_fn(out, y),
                      opt.Adam(learning_rate=2e-3))
    batch = 64
    for epoch in range(40):
        perm = onp.random.RandomState(epoch).permutation(len(Xtr))
        tot = 0.0
        for i in range(0, len(Xtr) - batch + 1, batch):
            xb = np.array(Xtr[perm[i:i + batch]])
            yb = np.array(Ytr[perm[i:i + batch]])
            tot += float(dp.step(xb, yb).asnumpy())
        if epoch % 5 == 0 or epoch == 39:
            pred = onp.argmax(net(np.array(Xte)).asnumpy(), axis=1)
            acc = (pred == Yte).mean()
            print(f"mobilenetv2 epoch {epoch}: loss {tot:.3f} "
                  f"test acc {acc:.4f}", flush=True)
    pred = onp.argmax(net(np.array(Xte)).asnumpy(), axis=1)
    acc = (pred == Yte).mean()
    assert acc >= 0.93, f"mobilenetv2 digits accuracy too low: {acc}"
    model_store.export_to_store(net, "mobilenetv2_0.25_digits", root=store_dir)
    print(f"registered mobilenetv2_0.25_digits (test acc {acc:.4f})")


def train_char_lm(store_dir):
    """Tiny LSTM char-LM on a deterministic synthetic corpus — the RNN
    serde artifact (embed + LSTM + dense head in one checkpoint)."""
    from incubator_mxnet_tpu.gluon.model_zoo import model_store

    rng = onp.random.RandomState(7)
    # synthetic 'language': markov chain over 28 symbols with sharp
    # transitions, so a real LM reduces perplexity well below uniform
    V = 28
    trans = rng.dirichlet(onp.ones(V) * 0.12, size=V)
    seq = [0]
    for _ in range(20000):
        seq.append(int(rng.choice(V, p=trans[seq[-1]])))
    data = onp.asarray(seq, onp.int32)

    class CharLM(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self.embed = gluon.nn.Embedding(V, 32)
            self.lstm = gluon.rnn.LSTM(64, num_layers=1, layout="NTC")
            self.head = gluon.nn.Dense(V, flatten=False)

        def forward(self, x):
            return self.head(self.lstm(self.embed(x)))

    from incubator_mxnet_tpu import optimizer as opt
    from incubator_mxnet_tpu.parallel.sharded import DataParallel

    mx.random.seed(0)
    net = CharLM()
    net.initialize()
    T, batch = 64, 32
    net(np.array(onp.zeros((2, T), "int32")))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    dp = DataParallel(net, lambda out, y: loss_fn(out, y),
                      opt.Adam(learning_rate=3e-3))
    uniform_nll = float(onp.log(V))
    last = None
    for step in range(300):
        starts = onp.random.RandomState(step).randint(
            0, len(data) - T - 1, size=batch)
        xb = onp.stack([data[s:s + T] for s in starts])
        yb = onp.stack([data[s + 1:s + T + 1] for s in starts])
        last = float(dp.step(np.array(xb), np.array(yb)).asnumpy())
        if step % 100 == 0:
            print(f"charlm step {step}: nll {last:.3f} "
                  f"(uniform {uniform_nll:.3f})", flush=True)
    assert last < 0.75 * uniform_nll, f"char-LM underfit: {last}"
    model_store.export_to_store(net, "lstm_charlm_tiny", root=store_dir)
    print(f"registered lstm_charlm_tiny (nll {last:.3f} vs uniform "
          f"{uniform_nll:.3f})")


def main():
    ap = argparse.ArgumentParser()
    default_store = os.path.join(os.path.dirname(__file__), "..",
                                 "incubator_mxnet_tpu", "gluon",
                                 "model_zoo", "_store")
    ap.add_argument("--store-dir", default=os.path.abspath(default_store))
    args = ap.parse_args()
    os.makedirs(args.store_dir, exist_ok=True)
    train_mobilenet_v2(args.store_dir)
    train_char_lm(args.store_dir)


if __name__ == "__main__":
    main()
