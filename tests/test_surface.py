"""Tests for the round-2 surface modules: fft, distribution, sparse, metric,
vision, hapi, profiler, autograd.PyLayer, text, audio, utils, device, moe."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn


class TestLazySurface:
    def test_every_advertised_module_imports(self):
        for m in paddle._LAZY_SUBMODULES:
            assert getattr(paddle, m) is not None


class TestFFT:
    def test_fft_roundtrip(self):
        from paddle_tpu import fft
        x = paddle.to_tensor(np.random.randn(8, 16).astype("float32"))
        y = fft.ifft(fft.fft(x))
        np.testing.assert_allclose(np.asarray(y._value.real), x.numpy(),
                                   atol=1e-5)

    def test_rfft_matches_numpy(self):
        from paddle_tpu import fft
        a = np.random.randn(16).astype("float32")
        got = np.asarray(fft.rfft(paddle.to_tensor(a))._value)
        np.testing.assert_allclose(got, np.fft.rfft(a), atol=1e-4)

    def test_fft2_and_shift(self):
        from paddle_tpu import fft
        a = np.random.randn(4, 8).astype("float32")
        got = np.asarray(fft.fftshift(fft.fft2(paddle.to_tensor(a)))._value)
        np.testing.assert_allclose(got, np.fft.fftshift(np.fft.fft2(a)),
                                   atol=1e-4)

    def test_rfft_grad(self):
        from paddle_tpu import fft
        x = paddle.to_tensor(np.random.randn(16).astype("float32"),
                             stop_gradient=False)
        y = fft.rfft(x)
        loss = (y._value.real ** 2).sum() + (y._value.imag ** 2).sum()
        # differentiate through the op surface instead: abs then sum
        z = fft.irfft(fft.rfft(x))
        z.sum().backward()
        assert x.grad is not None
        assert np.isfinite(x.grad.numpy()).all()


class TestDistribution:
    def test_normal_log_prob_entropy_kl(self):
        from paddle_tpu.distribution import Normal, kl_divergence
        n1 = Normal(0.0, 1.0)
        n2 = Normal(1.0, 2.0)
        lp = float(n1.log_prob(paddle.to_tensor(0.0)))
        np.testing.assert_allclose(lp, -0.9189385, atol=1e-5)
        ent = float(n1.entropy())
        np.testing.assert_allclose(ent, 1.4189385, atol=1e-5)
        kl = float(kl_divergence(n1, n2))
        assert kl > 0
        # closed form: log(s2/s1) + (s1^2+(m1-m2)^2)/(2 s2^2) - 0.5
        np.testing.assert_allclose(kl, np.log(2) + (1 + 1) / 8 - 0.5,
                                   atol=1e-5)

    def test_normal_sampling_moments(self):
        from paddle_tpu.distribution import Normal
        paddle.seed(0)
        s = Normal(3.0, 0.5).sample([20000]).numpy()
        np.testing.assert_allclose(s.mean(), 3.0, atol=0.05)
        np.testing.assert_allclose(s.std(), 0.5, atol=0.05)

    def test_rsample_differentiable(self):
        from paddle_tpu.distribution import Normal
        loc = paddle.to_tensor(np.float32(0.0), stop_gradient=False)
        d = Normal(loc, 1.0)
        d.rsample([16]).mean().backward()
        np.testing.assert_allclose(loc.grad.numpy(), 1.0, atol=1e-6)

    def test_categorical(self):
        from paddle_tpu.distribution import Categorical
        logits = paddle.to_tensor(np.log(np.asarray([0.7, 0.2, 0.1],
                                                    np.float32)))
        c = Categorical(logits)
        paddle.seed(0)
        s = c.sample([5000]).numpy()
        freq = np.bincount(s, minlength=3) / 5000
        np.testing.assert_allclose(freq, [0.7, 0.2, 0.1], atol=0.03)
        lp = c.log_prob(paddle.to_tensor(np.asarray([0])))
        np.testing.assert_allclose(lp.numpy(), [np.log(0.7)], atol=1e-5)

    def test_uniform_bernoulli(self):
        from paddle_tpu.distribution import Bernoulli, Uniform
        u = Uniform(2.0, 4.0)
        assert abs(float(u.entropy()) - np.log(2.0)) < 1e-5
        b = Bernoulli(paddle.to_tensor(np.float32(0.3)))
        lp = float(b.log_prob(paddle.to_tensor(np.float32(1.0))))
        np.testing.assert_allclose(lp, np.log(0.3), atol=1e-5)


class TestSparse:
    def test_coo_roundtrip(self):
        from paddle_tpu import sparse
        idx = np.array([[0, 1, 2], [1, 0, 2]])
        vals = np.array([1.0, 2.0, 3.0], np.float32)
        st = sparse.sparse_coo_tensor(idx, vals, [3, 3])
        dense = st.to_dense().numpy()
        expect = np.zeros((3, 3), np.float32)
        expect[idx[0], idx[1]] = vals
        np.testing.assert_array_equal(dense, expect)

    def test_csr_conversion(self):
        from paddle_tpu import sparse
        idx = np.array([[0, 0, 2], [0, 2, 1]])
        vals = np.array([1.0, 2.0, 3.0], np.float32)
        st = sparse.sparse_coo_tensor(idx, vals, [3, 3])
        csr = st.to_sparse_csr()
        np.testing.assert_array_equal(csr.crows().numpy(), [0, 2, 2, 3])
        np.testing.assert_array_equal(csr.to_dense().numpy(),
                                      st.to_dense().numpy())
        back = csr.to_sparse_coo()
        np.testing.assert_array_equal(back.to_dense().numpy(),
                                      st.to_dense().numpy())

    def test_sparse_math_and_grad(self):
        from paddle_tpu import sparse
        idx = np.array([[0, 1], [1, 0]])
        a = sparse.sparse_coo_tensor(idx, np.array([1.0, 2.0], np.float32),
                                     [2, 2])
        b = sparse.sparse_coo_tensor(idx, np.array([3.0, 4.0], np.float32),
                                     [2, 2])
        s = sparse.add(a, b)
        np.testing.assert_array_equal(s.to_dense().numpy(),
                                      [[0, 4], [6, 0]])
        dense = paddle.to_tensor(np.eye(2, dtype=np.float32))
        out = sparse.matmul(a, dense)
        np.testing.assert_array_equal(out.numpy(), [[0, 1], [2, 0]])

    def test_coalesce(self):
        from paddle_tpu import sparse
        idx = np.array([[0, 0], [1, 1]])  # duplicate coordinate
        st = sparse.sparse_coo_tensor(idx, np.array([1.0, 2.0], np.float32),
                                      [2, 2])
        c = st.coalesce()
        assert c.nnz() == 1
        np.testing.assert_allclose(c.values().numpy(), [3.0])


class TestMetric:
    def test_accuracy(self):
        from paddle_tpu.metric import Accuracy
        m = Accuracy()
        pred = paddle.to_tensor(np.asarray([[0.9, 0.1], [0.3, 0.7],
                                            [0.8, 0.2]], np.float32))
        label = paddle.to_tensor(np.asarray([[0], [1], [1]]))
        m.update(m.compute(pred, label))
        np.testing.assert_allclose(m.accumulate(), 2 / 3, atol=1e-6)
        m.reset()
        assert m.accumulate() == 0.0

    def test_precision_recall(self):
        from paddle_tpu.metric import Precision, Recall
        p, r = Precision(), Recall()
        preds = np.asarray([0.9, 0.8, 0.2, 0.6], np.float32)
        labels = np.asarray([1, 0, 1, 1])
        p.update(preds, labels)
        r.update(preds, labels)
        np.testing.assert_allclose(p.accumulate(), 2 / 3, atol=1e-6)
        np.testing.assert_allclose(r.accumulate(), 2 / 3, atol=1e-6)

    def test_auc_perfect_classifier(self):
        from paddle_tpu.metric import Auc
        auc = Auc()
        preds = np.asarray([0.9, 0.8, 0.1, 0.2], np.float32)
        labels = np.asarray([1, 1, 0, 0])
        auc.update(preds, labels)
        assert auc.accumulate() > 0.99


class TestVision:
    def test_transforms_pipeline(self):
        from paddle_tpu.vision import transforms as T
        img = (np.random.rand(40, 60, 3) * 255).astype(np.uint8)
        tf = T.Compose([T.Resize(32), T.CenterCrop(24), T.ToTensor(),
                        T.Normalize([0.5] * 3, [0.5] * 3)])
        out = tf(img)
        assert out.shape == (3, 24, 24)
        assert out.dtype == np.float32
        assert -1.01 <= out.min() and out.max() <= 1.01

    def test_resize_semantics(self):
        from paddle_tpu.vision import transforms as T
        img = np.zeros((10, 20, 3), np.uint8)
        assert T.resize(img, 5).shape == (5, 10, 3)  # short side
        assert T.resize(img, (7, 9)).shape == (7, 9, 3)

    def test_lenet_trains(self):
        from paddle_tpu.vision.models import LeNet
        from paddle_tpu.optimizer import Adam
        net = LeNet()
        opt = Adam(learning_rate=1e-3, parameters=net.parameters())
        x = paddle.to_tensor(np.random.randn(4, 1, 28, 28).astype("float32"))
        y = paddle.to_tensor(np.random.randint(0, 10, (4,)))
        import paddle_tpu.nn.functional as F
        losses = []
        for _ in range(3):
            loss = F.cross_entropy(net(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss))
        assert losses[-1] < losses[0]

    def test_resnet18_forward(self):
        from paddle_tpu.vision.models import resnet18
        net = resnet18(num_classes=7)
        net.eval()
        x = paddle.to_tensor(np.random.randn(2, 3, 64, 64).astype("float32"))
        out = net(x)
        assert list(out.shape) == [2, 7]

    def test_pretrained_raises(self):
        from paddle_tpu.vision.models import resnet50
        with pytest.raises(RuntimeError, match="hermetic"):
            resnet50(pretrained=True)

    def test_fake_dataset_with_loader(self):
        from paddle_tpu.vision.datasets import FakeImageDataset
        from paddle_tpu.io import DataLoader
        ds = FakeImageDataset(16, (3, 8, 8), 10)
        batch = next(iter(DataLoader(ds, batch_size=4)))
        assert list(batch[0].shape) == [4, 3, 8, 8]


class TestHapi:
    def _dataset(self, n=32):
        from paddle_tpu.io import TensorDataset
        rng = np.random.default_rng(0)
        x = rng.standard_normal((n, 8)).astype("float32")
        w = rng.standard_normal((8, 1)).astype("float32")
        y = (x @ w).astype("float32")
        return TensorDataset([x, y])

    def test_fit_decreases_loss(self):
        from paddle_tpu import Model
        import paddle_tpu.nn as nn
        net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 1))
        model = Model(net)
        from paddle_tpu.optimizer import Adam
        model.prepare(optimizer=Adam(learning_rate=1e-2,
                                     parameters=net.parameters()),
                      loss=nn.MSELoss())
        hist = model.fit(self._dataset(), batch_size=8, epochs=3, verbose=0)
        assert hist["loss"][-1] < hist["loss"][0]

    def test_evaluate_and_predict(self):
        from paddle_tpu import Model
        net = nn.Sequential(nn.Linear(8, 1))
        model = Model(net)
        model.prepare(loss=nn.MSELoss())
        logs = model.evaluate(self._dataset(16), batch_size=8, verbose=0)
        assert "loss" in logs
        preds = model.predict(self._dataset(16), batch_size=8,
                              stack_outputs=True)
        assert preds[0].shape == (16, 1)

    def test_summary(self):
        net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 1))
        stats = paddle.summary(net, (1, 8))
        assert stats["total_params"] == 8 * 16 + 16 + 16 * 1 + 1

    def test_early_stopping(self):
        from paddle_tpu import Model
        from paddle_tpu.callbacks import EarlyStopping
        net = nn.Sequential(nn.Linear(8, 1))
        model = Model(net)
        from paddle_tpu.optimizer import SGD
        model.prepare(optimizer=SGD(learning_rate=0.0,
                                    parameters=net.parameters()),
                      loss=nn.MSELoss())
        cb = EarlyStopping(monitor="loss", patience=1, verbose=0)
        model.fit(self._dataset(16), batch_size=8, epochs=10, verbose=0,
                  callbacks=[cb])
        assert model.stop_training  # zero lr -> no improvement -> stopped


class TestProfiler:
    def test_scheduler_states(self):
        from paddle_tpu.profiler import ProfilerState, make_scheduler
        sched = make_scheduler(closed=1, ready=1, record=2, repeat=1)
        states = [sched(i) for i in range(4)]
        assert states == [ProfilerState.CLOSED, ProfilerState.READY,
                          ProfilerState.RECORD,
                          ProfilerState.RECORD_AND_RETURN]
        assert sched(4) == ProfilerState.CLOSED  # repeat exhausted

    def test_profiler_timer_only(self, tmp_path):
        from paddle_tpu.profiler import Profiler, RecordEvent
        p = Profiler(timer_only=True, trace_dir=str(tmp_path))
        p.start()
        for _ in range(3):
            with RecordEvent("host_span"):
                pass
            p.step()
        p.stop()
        out = p.summary()
        assert "host_span" in out

    def test_record_event_standalone(self):
        from paddle_tpu.profiler import RecordEvent
        ev = RecordEvent("manual")
        ev.begin()
        ev.end()


class TestPyLayer:
    def test_custom_forward_backward(self):
        from paddle_tpu.autograd import PyLayer

        class CubeGrad(PyLayer):
            @staticmethod
            def forward(ctx, x):
                ctx.save_for_backward(x)
                return x * x

            @staticmethod
            def backward(ctx, g):
                (x,) = ctx.saved_tensor()
                return g * 3 * x * x  # deliberately NOT d(x^2): verify used

        x = paddle.to_tensor(np.asarray([2.0], np.float32),
                             stop_gradient=False)
        y = CubeGrad.apply(x)
        np.testing.assert_allclose(y.numpy(), [4.0])
        y.sum().backward()
        np.testing.assert_allclose(x.grad.numpy(), [12.0])  # 3*x^2

    def test_multi_output(self):
        from paddle_tpu.autograd import PyLayer

        class Split(PyLayer):
            @staticmethod
            def forward(ctx, x):
                return x * 2, x * 3

            @staticmethod
            def backward(ctx, g1, g2):
                return g1 * 2 + g2 * 3

        x = paddle.to_tensor(np.ones(3, np.float32), stop_gradient=False)
        a, b = Split.apply(x)
        (a.sum() + b.sum()).backward()
        np.testing.assert_allclose(x.grad.numpy(), [5.0] * 3)  # g1*2 + g2*3


class TestTextAudio:
    def test_viterbi_simple(self):
        from paddle_tpu.text import viterbi_decode
        # 2 tags; potentials strongly prefer tag 1 at every step
        pot = np.zeros((1, 3, 2), np.float32)
        pot[:, :, 1] = 5.0
        trans = np.zeros((2, 2), np.float32)
        lens = np.asarray([3])
        scores, path = viterbi_decode(
            paddle.to_tensor(pot), paddle.to_tensor(trans),
            paddle.to_tensor(lens), include_bos_eos_tag=False)
        np.testing.assert_array_equal(path.numpy(), [[1, 1, 1]])
        np.testing.assert_allclose(float(scores.numpy()[0]), 15.0, atol=1e-5)

    def test_mel_spectrogram_shapes(self):
        from paddle_tpu.audio import MelSpectrogram
        layer = MelSpectrogram(sr=8000, n_fft=256, n_mels=32)
        x = paddle.to_tensor(np.random.randn(2, 4000).astype("float32"))
        out = layer(x)
        assert list(out.shape)[0:2] == [2, 32]

    def test_fbank_rows_nonneg(self):
        from paddle_tpu.audio.functional import compute_fbank_matrix
        fb = compute_fbank_matrix(8000, 256, n_mels=20).numpy()
        assert fb.shape == (20, 129)
        assert (fb >= 0).all() and fb.sum() > 0


class TestUtilsDevice:
    def test_unique_name(self):
        from paddle_tpu.utils import unique_name
        with unique_name.guard():
            assert unique_name.generate("fc") == "fc_0"
            assert unique_name.generate("fc") == "fc_1"
        with unique_name.guard():
            assert unique_name.generate("fc") == "fc_0"

    def test_run_check(self, capsys):
        paddle.utils.run_check()
        assert "successfully" in capsys.readouterr().out

    def test_device_queries(self):
        from paddle_tpu import device
        assert device.device_count() >= 1
        assert not device.cuda.is_available()
        assert device.cuda.device_count() == 0

    def test_tpu_place_without_a_chip_is_an_error(self):
        """A TPUPlace never resolves to a CPU device (ISSUE 21)."""
        from paddle_tpu import device
        from paddle_tpu.core import place
        with pytest.raises(RuntimeError, match="no accelerator"):
            place.get_jax_device(paddle.TPUPlace(0))
        with pytest.raises(RuntimeError, match="no accelerator"):
            paddle.to_tensor([1.0], place=paddle.TPUPlace(0))
        assert place.get_jax_device(paddle.CPUPlace()).platform == "cpu"
        device.synchronize()                  # a barrier, on any backend

    def test_static_shim(self):
        from paddle_tpu import static
        assert static.InputSpec([None, 8]).shape == [None, 8]
        # r5: Program/Executor are REAL now (static/program.py op-tape
        # tier) — constructing one must not raise
        prog = static.Program()
        assert prog.ops == []

    def test_version(self):
        from paddle_tpu import version
        assert version.full_version


class TestMoE:
    def test_routing_output_and_aux(self):
        from paddle_tpu.distributed.moe import MoELayer
        d = 16
        experts = [nn.Sequential(nn.Linear(d, 32), nn.GELU(),
                                 nn.Linear(32, d)) for _ in range(4)]
        moe = MoELayer(d_model=d, experts=experts,
                       gate={"type": "gshard", "capacity_factor": 8.0})
        x = paddle.to_tensor(np.random.randn(2, 6, d).astype("float32"),
                             stop_gradient=False)
        y = moe(x)
        assert list(y.shape) == [2, 6, d]
        assert moe.aux_loss is not None and np.isfinite(float(moe.aux_loss))
        (y ** 2).mean().backward()
        assert moe.gate.weight.grad is not None
        # identical experts are consolidated into stacked [E, ...] Parameters
        assert moe._stacked is not None
        grads = [p.grad for p in moe._stacked]
        assert all(g is not None for g in grads)
        assert all(g.shape[0] == 4 for g in grads)

    def test_top1_switch_with_huge_capacity_matches_dense_expert(self):
        """With capacity >= tokens and top-1 routing, each token's output is
        exactly its chosen expert's output (oracle check)."""
        from paddle_tpu.distributed.moe import MoELayer
        d = 8
        experts = [nn.Linear(d, d) for _ in range(2)]
        moe = MoELayer(d_model=d, experts=experts,
                       gate={"type": "switch", "capacity_factor": 100.0})
        x = paddle.to_tensor(np.random.randn(1, 5, d).astype("float32"))
        y = moe(x).numpy()[0]
        logits = x.numpy()[0] @ moe.gate.weight.numpy()
        choice = logits.argmax(-1)
        for t in range(5):
            e = experts[choice[t]]
            expect = x.numpy()[0][t] @ e.weight.numpy() + e.bias.numpy()
            np.testing.assert_allclose(y[t], expect, atol=1e-5)

    def test_capacity_drops_tokens(self):
        from paddle_tpu.distributed.moe import GShardGate
        gate = GShardGate(4, 2, capacity_factor=0.25)
        cap = gate.capacity(8)  # 8 tokens * 0.25 * 2 / 2 = 2
        assert cap == 2


class TestInferencePredictor:
    def test_save_then_predict(self, tmp_path):
        import paddle_tpu.nn as nn
        from paddle_tpu import inference
        from paddle_tpu.jit import InputSpec, save

        net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
        net.eval()
        path = str(tmp_path / "model")
        save(net, path, input_spec=[InputSpec([None, 8], "float32", "x")])

        cfg = inference.Config(path)
        cfg.enable_memory_optim()
        pred = inference.create_predictor(cfg)
        assert pred.get_input_names() == ["x"]
        x = np.random.randn(3, 8).astype("float32")
        h = pred.get_input_handle("x")
        h.copy_from_cpu(x)
        pred.run()
        out = pred.get_output_handle("out0").copy_to_cpu()
        expect = net(paddle.to_tensor(x)).numpy()
        np.testing.assert_allclose(out, expect, atol=1e-5)

    def test_run_list_api(self, tmp_path):
        import paddle_tpu.nn as nn
        from paddle_tpu import inference
        from paddle_tpu.jit import InputSpec, save
        net = nn.Linear(4, 2)
        net.eval()
        path = str(tmp_path / "m2")
        save(net, path, input_spec=[InputSpec([None, 4], "float32")])
        pred = inference.create_predictor(inference.Config(path))
        x = np.random.randn(2, 4).astype("float32")
        outs = pred.run([x])
        np.testing.assert_allclose(outs[0], net(paddle.to_tensor(x)).numpy(),
                                   atol=1e-5)

    def test_noop_knobs_warn_once(self):
        """r2 VERDICT weak#7: GPU/TRT/MKLDNN knobs must not be silent."""
        import warnings
        from paddle_tpu import inference
        inference._noop_warn._seen.discard("enable_tensorrt_engine")
        cfg = inference.Config("m")
        with pytest.warns(UserWarning, match="XLA performs the fusion"):
            cfg.enable_tensorrt_engine()
        with warnings.catch_warnings():     # second call: silent
            warnings.simplefilter("error")
            cfg.enable_tensorrt_engine()

    def test_config_and_predictor_clone(self, tmp_path):
        import paddle_tpu.nn as nn
        from paddle_tpu import inference
        from paddle_tpu.jit import InputSpec, save
        net = nn.Linear(4, 2)
        net.eval()
        path = str(tmp_path / "m3")
        save(net, path, input_spec=[InputSpec([None, 4], "float32")])
        cfg = inference.Config(path)
        cfg2 = cfg.clone()
        assert cfg2.model_dir() == cfg.model_dir()
        pred = inference.create_predictor(cfg2)
        p2 = pred.clone()                    # shares weights, separate IO
        x = np.random.randn(2, 4).astype("float32")
        out1 = pred.run([x])[0]
        out2 = p2.run([x * 2])[0]
        np.testing.assert_allclose(out1, net(paddle.to_tensor(x)).numpy(),
                                   atol=1e-5)
        np.testing.assert_allclose(
            out2, net(paddle.to_tensor(x * 2)).numpy(), atol=1e-5)


def _rpc_double(x):
    return x * 2


def _rpc_raise():
    raise ValueError("remote boom")


class TestRPC:
    def test_sync_async_and_errors(self):
        from paddle_tpu.distributed import rpc
        import multiprocessing as mp
        from paddle_tpu.native import TCPStore
        # reserve a port by binding a store briefly
        probe = TCPStore(is_master=True)
        port = probe.port
        probe.close()
        ep = f"127.0.0.1:{port}"

        def child():
            from paddle_tpu.distributed import rpc as r
            r.init_rpc("worker1", rank=1, world_size=2, master_endpoint=ep)
            r.shutdown()

        p = mp.get_context("fork").Process(target=child)
        p.start()
        rpc.init_rpc("worker0", rank=0, world_size=2, master_endpoint=ep)
        try:
            assert rpc.rpc_sync("worker1", _rpc_double, args=(21,)) == 42
            fut = rpc.rpc_async("worker1", _rpc_double, args=(5,))
            assert fut.wait() == 10
            # self-call works too
            assert rpc.rpc_sync("worker0", _rpc_double, args=(1,)) == 2
            with pytest.raises(RuntimeError, match="remote boom"):
                rpc.rpc_sync("worker1", _rpc_raise)
            infos = rpc.get_all_worker_infos()
            assert [w.name for w in infos] == ["worker0", "worker1"]
        finally:
            rpc.shutdown()
            p.join(timeout=30)
        assert p.exitcode == 0


class TestEnforce:
    def test_error_types_and_context(self):
        from paddle_tpu.core import enforce as E
        with pytest.raises(E.EnforceNotMet, match="error code"):
            E.enforce(False, "broken invariant")
        with pytest.raises(E.InvalidArgumentError, match="expected 1"):
            E.enforce_eq(1, 2)
        with pytest.raises(E.InvalidArgumentError):
            E.enforce_gt(1, 2)
        with pytest.raises(E.NotFoundError):
            E.enforce_not_none(None, "missing thing")
        assert E.enforce_not_none(5) == 5
        try:
            E.enforce(False, "ctx check")
        except E.EnforceNotMet as e:
            assert "test_surface.py" in str(e)  # calling frame recorded

    def test_signal_handlers_installed(self):
        import faulthandler
        from paddle_tpu.core import enforce as E
        E.install_signal_handlers()
        assert faulthandler.is_enabled()


def _rpc_big(n):
    return np.zeros(n, np.uint8) + 7


class TestReviewFixesRound2b:
    def test_trapezoid_dx_zero(self):
        y = paddle.to_tensor(np.asarray([1.0, 2.0, 3.0], np.float32))
        assert float(paddle.trapezoid(y, dx=0.0)) == 0.0

    def test_tcp_store_large_value(self):
        from paddle_tpu.native import TCPStore
        s = TCPStore(is_master=True)
        try:
            big = bytes(range(256)) * (8 * 1024)  # 2MB > 1MB probe buffer
            s.set("big", big)
            assert s.get("big") == big
        finally:
            s.close()

    def test_rpc_large_payload_and_cleanup(self):
        from paddle_tpu.distributed import rpc
        from paddle_tpu.native import TCPStore
        probe = TCPStore(is_master=True)
        port = probe.port
        probe.close()
        rpc.init_rpc("solo", rank=0, world_size=1,
                     master_endpoint=f"127.0.0.1:{port}")
        try:
            out = rpc.rpc_sync("solo", _rpc_big, args=(3 * 1024 * 1024,))
            assert out.shape == (3 * 1024 * 1024,) and out[0] == 7
            # req/res keys cleaned up after the exchange
            assert not rpc._client().check("__rpc/solo/req/0")
            assert not rpc._client().check("__rpc/solo/res/0")
        finally:
            rpc.shutdown()

    def test_histogramdd_edges_consistent(self):
        x = np.random.randn(100, 2).astype("float32")
        hist, edges = paddle.histogramdd(paddle.to_tensor(x), bins=5)
        ref_h, ref_e = np.histogramdd(x, bins=5)
        np.testing.assert_allclose(hist.numpy(), ref_h, atol=1e-5)
        for e, re_ in zip(edges, ref_e):
            np.testing.assert_allclose(e.numpy(), re_, atol=1e-4)

    def test_as_complex_single_source(self):
        from paddle_tpu.ops import extras, manipulation
        assert extras.view_as_complex is manipulation.as_complex


class TestIncubateFusedFunctional:
    def test_fused_rope_matches_kernel(self):
        from paddle_tpu.incubate.nn import functional as IF
        from paddle_tpu.kernels.rope import apply_rope, rope_cos_sin
        q = np.random.randn(1, 8, 2, 16).astype("float32")
        k = np.random.randn(1, 8, 2, 16).astype("float32")
        oq, ok, ov = IF.fused_rotary_position_embedding(
            paddle.to_tensor(q), paddle.to_tensor(k))
        cos, sin = rope_cos_sin(8, 16)
        np.testing.assert_allclose(oq.numpy(),
                                   np.asarray(apply_rope(jnp.asarray(q),
                                                         cos, sin)),
                                   atol=1e-5)
        assert ov is None

    def test_fused_rms_norm(self):
        from paddle_tpu.incubate.nn import functional as IF
        x = np.random.randn(4, 16).astype("float32")
        w = np.random.rand(16).astype("float32")
        got = IF.fused_rms_norm(paddle.to_tensor(x),
                                paddle.to_tensor(w)).numpy()
        ref = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-6) * w
        np.testing.assert_allclose(got, ref, atol=1e-5)

    def test_swiglu(self):
        from paddle_tpu.incubate.nn import functional as IF
        x = np.random.randn(3, 8).astype("float32")
        got = IF.swiglu(paddle.to_tensor(x)).numpy()
        a, b = x[:, :4], x[:, 4:]
        ref = (a / (1 + np.exp(-a))) * b
        np.testing.assert_allclose(got, ref, atol=1e-5)

    def test_fused_mha_runs_and_grads(self):
        from paddle_tpu.incubate.nn import functional as IF
        E, H = 16, 4
        x = paddle.to_tensor(np.random.randn(2, 8, E).astype("float32"),
                             stop_gradient=False)
        qkv_w = paddle.to_tensor(
            np.random.randn(E, 3 * E).astype("float32") / 4,
            stop_gradient=False)
        out = IF.fused_multi_head_attention(x, qkv_w, num_heads=H,
                                            causal=True, training=False)
        assert list(out.shape) == [2, 8, E]
        out.sum().backward()
        assert x.grad is not None and qkv_w.grad is not None


class TestLBFGS:
    def test_converges_on_quadratic(self):
        from paddle_tpu.core.tensor import Parameter
        from paddle_tpu.optimizer import LBFGS
        target = np.asarray([1.0, -2.0, 3.0], np.float32)
        w = Parameter(np.zeros(3, np.float32))
        opt = LBFGS(learning_rate=1.0, max_iter=10, parameters=[w])

        def closure():
            opt.clear_grad()
            loss = ((w - paddle.to_tensor(target)) ** 2).sum()
            loss.backward()
            return loss

        loss = opt.step(closure)
        assert float(loss) < 1e-6
        np.testing.assert_allclose(w.numpy(), target, atol=1e-3)

    def test_rosenbrock_descends(self):
        from paddle_tpu.core.tensor import Parameter
        from paddle_tpu.optimizer import LBFGS
        w = Parameter(np.asarray([-1.0, 1.0], np.float32))
        opt = LBFGS(learning_rate=0.5, max_iter=30, parameters=[w])

        def closure():
            opt.clear_grad()
            a, b = w[0], w[1]
            loss = (1 - a) ** 2 + 100 * (b - a ** 2) ** 2
            loss.backward()
            return loss

        first = float(closure())
        loss = opt.step(closure)
        assert float(loss) < first * 0.05


class TestSparseAttention:
    """r4: sparse.nn.functional.attention (CSR-masked SDPA; ref:
    paddle.sparse.nn.functional.attention)."""

    def test_csr_mask_matches_dense_oracle(self):
        import paddle_tpu.sparse as sparse
        rng = np.random.default_rng(0)
        B, H, S, D = 2, 2, 8, 4
        q = paddle.to_tensor(rng.standard_normal((B, H, S, D)).astype(
            np.float32))
        k = paddle.to_tensor(rng.standard_normal((B, H, S, D)).astype(
            np.float32))
        v = paddle.to_tensor(rng.standard_normal((B, H, S, D)).astype(
            np.float32))
        dense = np.tril(np.ones((S, S), np.float32))
        coo = sparse.sparse_coo_tensor(np.stack(np.nonzero(dense)),
                                       dense[dense > 0], (S, S))
        out = sparse.attention(q, k, v, coo.to_sparse_csr())
        s = np.einsum("bhqd,bhkd->bhqk", q.numpy(), k.numpy()) / np.sqrt(D)
        s = np.where(dense[None, None] > 0, s, -1e30)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        ref = np.einsum("bhqk,bhkd->bhqd", p, v.numpy())
        np.testing.assert_allclose(out.numpy(), ref, atol=2e-5)

    def test_key_padding_mask_and_namespace(self):
        import paddle_tpu.sparse as sparse
        rng = np.random.default_rng(1)
        B, H, S, D = 1, 2, 6, 4
        q = paddle.to_tensor(rng.standard_normal((B, H, S, D)).astype(
            np.float32))
        dense = np.ones((S, S), np.float32)
        csr = sparse.sparse_coo_tensor(
            np.stack(np.nonzero(dense)), dense[dense > 0],
            (S, S)).to_sparse_csr()
        kp = np.ones((B, S), np.float32)
        kp[:, -2:] = 0
        out = sparse.nn.functional.attention(
            q, q, q, csr, key_padding_mask=paddle.to_tensor(kp))
        # padded keys receive zero attention: output equals attention over
        # the first S-2 keys only
        s = np.einsum("bhqd,bhkd->bhqk", q.numpy(), q.numpy()) / np.sqrt(D)
        s = s[..., :4]
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        ref = np.einsum("bhqk,bhkd->bhqd", p, q.numpy()[:, :, :4])
        np.testing.assert_allclose(out.numpy(), ref, atol=2e-5)
