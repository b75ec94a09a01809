"""Submodule surface completeness + behavior of the long-tail additions
(text datasets, incubate optimizers, vision transforms/factories/yolo_loss,
static compat, optimizer NAdam/RAdam/LBFGS, sparse/linalg/geometric gaps,
LKJCholesky, audio backends, nn.utils)."""
import os
import re

import numpy as np
import pytest
import torch

import paddle_tpu as paddle
import paddle_tpu.nn as nn


def t(a):
    return paddle.to_tensor(np.asarray(a))


rs = np.random.RandomState(0)

_SWEEP = ["amp", "audio", "autograd", "device", "distribution", "fft",
          "geometric", "incubate", "inference", "io", "jit", "linalg",
          "metric", "nn.initializer", "optimizer", "profiler",
          "regularizer", "sparse", "static", "text", "vision.transforms",
          "vision.models", "quantization", "utils", "hub", "nn.functional",
          "nn.utils", "sysconfig"]


class TestSurfaceCompleteness:
    @pytest.mark.parametrize("mod", _SWEEP)
    def test_no_missing_exports(self, mod):
        import importlib
        ref_path = ("/root/reference/python/paddle/"
                    + mod.replace(".", "/") + "/__init__.py")
        if not os.path.exists(ref_path):
            ref_path = ("/root/reference/python/paddle/"
                        + mod.replace(".", "/") + ".py")
        if not os.path.exists(ref_path):
            pytest.skip("no reference file")
        ref = open(ref_path).read()
        names = sorted(
            set(re.findall(r"^\s+'(\w+)',?$", ref, re.M))
            | set(re.findall(r'^\s+"(\w+)",?$', ref, re.M)))
        if not names:
            pytest.skip("no __all__ list")
        mine = importlib.import_module("paddle_tpu." + mod)
        missing = [n for n in names
                   if not n.startswith("_") and not hasattr(mine, n)]
        assert missing == [], missing


class TestTextDatasets:
    def test_wmt_parallel_corpus(self, tmp_path):
        f = tmp_path / "train.txt"
        f.write_text("the cat\tle chat\nthe dog runs\tle chien court\n")
        from paddle_tpu.text import WMT14
        ds = WMT14(data_file=str(f), mode="train", dict_size=50)
        assert len(ds) == 2
        src, trg, trg_next = ds[1]
        assert src.shape[0] == 3 and trg.shape[0] == 4
        assert trg[0] == 0                       # <s>
        assert trg_next[-1] == 1                 # <e>
        d = ds.get_dict("en")
        assert d["<unk>"] == 2 and "cat" in d
        rev = ds.get_dict("fr", reverse=True)
        assert rev[d["<unk>"]] == "<unk>"
        assert "chat" in ds.get_dict("fr")


class TestIncubate:
    def test_lookahead_pulls_to_slow(self):
        import paddle_tpu.optimizer as optim
        from paddle_tpu.incubate import LookAhead
        w = paddle.create_parameter([4])
        inner = optim.SGD(learning_rate=0.1, parameters=[w])
        la = LookAhead(inner, alpha=0.5, k=2)
        for _ in range(4):
            loss = (w * w).sum()
            loss.backward()
            la.step()
            la.clear_grad()
        assert np.isfinite(w.numpy()).all()

    def test_model_average_apply_restore(self):
        from paddle_tpu.incubate import ModelAverage
        w = paddle.create_parameter([2])
        w.set_value(t(np.array([2.0, 4.0], np.float32)))
        ma = ModelAverage(parameters=[w])
        ma.step()
        w.set_value(t(np.array([4.0, 8.0], np.float32)))
        ma.step()
        with ma:
            np.testing.assert_allclose(w.numpy(), [3.0, 6.0])
        np.testing.assert_allclose(w.numpy(), [4.0, 8.0])

    def test_segment_aliases(self):
        import paddle_tpu.incubate as inc
        out = inc.segment_sum(t(np.array([1., 2., 3.], np.float32)),
                              t(np.array([0, 0, 1], np.int32)))
        assert out.numpy().tolist() == [3.0, 3.0]


class TestVisionAdditions:
    def test_yolo_loss_differentiable(self):
        import paddle_tpu.vision.ops as vops
        N, M, C, H, W = 1, 3, 4, 4, 4
        x = t(rs.randn(N, M * (5 + C), H, W).astype(np.float32))
        x.stop_gradient = False
        gt = t(np.array([[[0.5, 0.5, 0.4, 0.4]]], np.float32))
        lb = t(np.array([[1]], np.int32))
        loss = vops.yolo_loss(x, gt, lb, [10, 13, 16, 30, 33, 23],
                              [0, 1, 2], C, 0.7, 32)
        assert loss.shape == [N]
        loss.sum().backward()
        assert np.isfinite(x.grad.numpy()).all()
        assert abs(x.grad.numpy()).max() > 0

    def test_roi_layers(self):
        import paddle_tpu.vision.ops as vops
        x = t(rs.randn(1, 4, 16, 16).astype(np.float32))
        boxes = t(np.array([[0, 0, 8, 8]], np.float32))
        bn = t(np.array([1], np.int32))
        assert vops.RoIAlign(2)(x, boxes, bn).shape == [1, 4, 2, 2]
        assert vops.RoIPool(2)(x, boxes, bn).shape == [1, 4, 2, 2]
        assert vops.PSRoIPool(2)(x, boxes, bn).shape == [1, 1, 2, 2]

    def test_transforms_functional_invariants(self):
        import paddle_tpu.vision.transforms as T
        img = (rs.rand(20, 30, 3) * 255).astype(np.uint8)
        assert np.array_equal(T.hflip(T.hflip(img)), img)
        assert T.rotate(img, 90, expand=True).shape[:2] == (30, 20)
        r = T.rotate(img.astype(np.float32), 360.0,
                     interpolation="bilinear")
        assert abs(r[5:-5, 5:-5] - img[5:-5, 5:-5]).max() < 2.0
        pts = [(0, 0), (29, 0), (29, 19), (0, 19)]
        p = T.perspective(img.astype(np.float32), pts, pts,
                          interpolation="bilinear")
        assert abs(p - img).max() < 1.0
        assert T.adjust_hue(img, 0.0).shape == img.shape
        with pytest.raises(ValueError):
            T.adjust_hue(img, 0.9)
        assert T.to_grayscale(img, 3).shape == img.shape

    def test_transform_classes_run(self):
        import paddle_tpu.vision.transforms as T
        img = (rs.rand(16, 16, 3) * 255).astype(np.uint8)
        pipeline = T.Compose([
            T.ColorJitter(0.4, 0.4, 0.4, 0.2), T.RandomRotation(10),
            T.RandomAffine(5, translate=(0.1, 0.1)),
            T.RandomPerspective(prob=1.0), T.RandomVerticalFlip(1.0),
            T.RandomErasing(prob=1.0), T.Grayscale(3), T.Pad(2),
            T.Transpose(),
        ])
        out = pipeline(img)
        assert out.shape == (3, 20, 20)

    @pytest.mark.parametrize(
        "factory", ["resnext50_32x4d", "shufflenet_v2_x0_5", "densenet169"])
    def test_model_factories(self, factory):
        import paddle_tpu.vision.models as M
        x = t(rs.randn(1, 3, 32, 32).astype(np.float32))
        # one compiled forward, not a compile for every layer's shape
        m = paddle.jit.to_static(getattr(M, factory)(num_classes=7))
        m.eval()
        assert m(x).shape == [1, 7]


class TestStaticCompat:
    def test_gradients_eager_equivalent(self):
        import paddle_tpu.static as st
        x = t(np.array([1., 2.], np.float32))
        x.stop_gradient = False
        g = st.gradients([(x * x).sum()], [x])
        np.testing.assert_allclose(g[0].numpy(), [2.0, 4.0])

    def test_ema_apply_restore(self):
        import paddle_tpu.static as st
        w = paddle.create_parameter([2])
        w.set_value(t(np.array([1.0, 1.0], np.float32)))
        ema = st.ExponentialMovingAverage(0.5)
        ema.update([w])
        backup = w.numpy().copy()
        with ema.apply():
            pass
        np.testing.assert_allclose(w.numpy(), backup)

    def test_program_machinery_is_real(self):
        """r5: Program/program_guard/Executor are a real deferred-graph
        builder (tests/test_static_program.py covers behavior); here just
        the namespace contracts."""
        import paddle_tpu.static as st
        with pytest.raises(ValueError):
            st.Executor().run()            # no active/passed Program
        p = st.Program()
        assert st.CompiledProgram(p).program is p
        bs = st.BuildStrategy()
        bs.fuse_bn_act_ops = True
        assert bs.fuse_bn_act_ops is True

    def test_places(self):
        import paddle_tpu.static as st
        assert len(st.cpu_places(2)) == 2
        assert st.cuda_places() != []


class TestOptimizerAdditions:
    def _quad(self, mine_cls, torch_cls, steps=25):
        w = paddle.create_parameter([4])
        w.set_value(t(np.ones(4, np.float32)))
        opt = mine_cls(learning_rate=0.1, parameters=[w])
        for _ in range(steps):
            loss = (w * w).sum()
            loss.backward()
            opt.step()
            opt.clear_grad()
        wt = torch.nn.Parameter(torch.ones(4))
        topt = torch_cls([wt], lr=0.1)
        for _ in range(steps):
            topt.zero_grad()
            (wt * wt).sum().backward()
            topt.step()
        return w.numpy(), wt.detach().numpy()

    def test_nadam_matches_torch(self):
        import paddle_tpu.optimizer as optim
        a, b = self._quad(optim.NAdam, torch.optim.NAdam)
        np.testing.assert_allclose(a, b, atol=1e-5)

    def test_radam_matches_torch(self):
        import paddle_tpu.optimizer as optim
        a, b = self._quad(optim.RAdam, torch.optim.RAdam)
        np.testing.assert_allclose(a, b, atol=1e-4)

    def test_lbfgs_converges(self):
        import paddle_tpu.optimizer as optim
        w = paddle.create_parameter([2])
        w.set_value(t(np.array([3.0, -2.0], np.float32)))
        opt = optim.LBFGS(learning_rate=0.5, max_iter=30,
                          line_search_fn="strong_wolfe", parameters=[w])
        target = t(np.array([1.0, 2.0], np.float32))

        def closure():
            opt.clear_grad()
            loss = ((w - target) ** 2).sum()
            loss.backward()
            return loss

        loss = opt.step(closure)
        np.testing.assert_allclose(w.numpy(), [1.0, 2.0], atol=1e-4)

    def test_linear_lr(self):
        import paddle_tpu.optimizer as optim
        sch = optim.lr.LinearLR(0.1, total_steps=10, start_factor=0.5)
        assert abs(sch.get_lr() - 0.05) < 1e-9
        for _ in range(10):
            sch.step()
        assert abs(sch.get_lr() - 0.1) < 1e-9


class TestSparseLinalgGeometric:
    def test_sparse_additions(self):
        import paddle_tpu.sparse as sp
        d = np.zeros((4, 5), np.float32)
        d[0, 1], d[2, 3] = 2, -1
        coo = sp.to_sparse_coo(t(d), 2)
        assert sp.reshape(coo, [2, 10]).to_dense().shape == [2, 10]
        assert sp.slice(coo, [0], [1], [4]).to_dense().shape == [3, 5]
        y = t(np.ones((5, 3), np.float32))
        am = sp.addmm(t(np.ones((4, 3), np.float32)), coo, y,
                      beta=0.5, alpha=2.0)
        np.testing.assert_allclose(
            am.numpy(), 0.5 + 2.0 * (d @ np.ones((5, 3))), rtol=1e-6)
        m = sp.mask_as(t(np.arange(20, dtype=np.float32).reshape(4, 5)),
                       coo)
        assert float(m.to_dense().numpy()[0, 1]) == 1.0
        assert not bool(sp.isnan(coo).to_dense().numpy().any())

    def test_cholesky_inverse(self):
        import paddle_tpu.linalg as la
        A = rs.randn(4, 4).astype(np.float32)
        A = A @ A.T + 4 * np.eye(4, dtype=np.float32)
        L = np.linalg.cholesky(A)
        np.testing.assert_allclose(la.cholesky_inverse(t(L)).numpy(),
                                   np.linalg.inv(A), atol=1e-4)

    def test_weighted_sample_neighbors(self):
        import paddle_tpu.geometric as g
        row = t(np.array([1, 2, 0, 2, 0, 1], np.int64))
        colptr = t(np.array([0, 2, 4, 6], np.int64))
        w = t(np.array([1., 1000., 1., 1., 1., 1.], np.float32))
        nb, cnt = g.weighted_sample_neighbors(
            row, colptr, w, t(np.array([0], np.int64)), sample_size=1)
        assert int(nb.numpy()[0]) == 2      # overwhelming weight

    def test_reindex_heter_graph(self):
        import paddle_tpu.geometric as g
        rn, dst, nodes = g.reindex_heter_graph(
            t(np.array([5, 7], np.int64)),
            [t(np.array([7, 9], np.int64))],
            [t(np.array([1, 1], np.int64))])
        assert nodes.numpy().tolist() == [5, 7, 9]
        assert rn.numpy().tolist() == [1, 2]


class TestLKJCholesky:
    def test_samples_valid_and_log_prob_matches_torch(self):
        from paddle_tpu.distribution import LKJCholesky
        d = LKJCholesky(3, concentration=1.5)
        L = d.sample((200,)).numpy()
        np.testing.assert_allclose((L ** 2).sum(-1), 1.0, atol=1e-5)
        assert abs(np.triu(L, 1)).max() < 1e-6
        tor = torch.distributions.LKJCholesky(3, concentration=1.5)
        ref = tor.log_prob(torch.tensor(L[:5])).numpy()
        got = d.log_prob(t(L[:5])).numpy()
        np.testing.assert_allclose(got, ref, atol=1e-5)

    def test_marginals_match_lkj_theory(self):
        # LKJ(eta) marginal: r ~ 2 Beta(a, a) - 1 with a = eta - 1 + d/2;
        # every off-diagonal is exchangeable.  (Checked against theory,
        # not torch: torch's .sample is measurably non-exchangeable.)
        from paddle_tpu.distribution import LKJCholesky
        L = LKJCholesky(3, concentration=1.5).sample((4000,)).numpy()
        C = L @ np.transpose(L, (0, 2, 1))
        a = 1.5 - 1 + 3 / 2
        std = np.sqrt(4 * a * a / ((2 * a) ** 2 * (2 * a + 1)))
        for (i, j) in ((1, 0), (2, 0), (2, 1)):
            r = C[:, i, j]
            assert abs(r.mean()) < 0.03
            assert abs(r.std() - std) < 0.02, (i, j, r.std())

    def test_dim2_eta1_uniform(self):
        from paddle_tpu.distribution import LKJCholesky
        from scipy import stats
        L = LKJCholesky(2, 1.0).sample((4000,)).numpy()
        ks = stats.kstest(L[:, 1, 0],
                          stats.uniform(loc=-1, scale=2).cdf)
        assert ks.pvalue > 0.01


class TestAudioBackends:
    def test_save_info_load_roundtrip(self, tmp_path):
        import paddle_tpu.audio as audio
        path = str(tmp_path / "tone_happy.wav")
        wav = (np.sin(np.linspace(0, 440 * 2 * np.pi, 8000))
               .astype(np.float32) * 0.5)
        audio.save(path, wav, 16000)
        i = audio.info(path)
        assert (i.sample_rate, i.num_samples, i.num_channels) == \
            (16000, 8000, 1)
        data, sr = audio.load(path)
        assert sr == 16000
        np.testing.assert_allclose(data, wav, atol=1e-4)

    def test_tess_dataset_labels_from_filenames(self, tmp_path):
        import paddle_tpu.audio as audio
        wav = np.zeros(100, np.float32)
        audio.save(str(tmp_path / "x_angry.wav"), wav, 8000)
        audio.save(str(tmp_path / "x_sad.wav"), wav, 8000)
        ds = audio.datasets.TESS(str(tmp_path), split_ratio=1.0)
        labels = sorted(int(ds[i][1]) for i in range(len(ds)))
        assert labels == [audio.datasets.TESS.EMOTIONS.index("angry"),
                          audio.datasets.TESS.EMOTIONS.index("sad")]


class TestNNUtils:
    def test_weight_norm_preserves_function(self):
        from paddle_tpu.nn.utils import weight_norm, remove_weight_norm
        layer = nn.Linear(4, 3)
        x = t(rs.randn(2, 4).astype(np.float32))
        y0 = layer(x).numpy()
        weight_norm(layer, "weight", dim=0)
        np.testing.assert_allclose(layer(x).numpy(), y0, atol=1e-5)
        assert "weight_g" in layer._parameters
        remove_weight_norm(layer)
        np.testing.assert_allclose(layer(x).numpy(), y0, atol=1e-5)
        assert "weight" in layer._parameters

    def test_spectral_norm_converges_to_unit_sv(self):
        from paddle_tpu.nn.utils import spectral_norm
        layer = nn.Linear(4, 3)
        spectral_norm(layer, "weight", n_power_iterations=2)
        x = t(rs.randn(2, 4).astype(np.float32))
        for _ in range(20):
            layer(x)
        sv = np.linalg.svd(np.asarray(layer.weight._data),
                           compute_uv=False)[0]
        assert abs(sv - 1.0) < 1e-3

    def test_clip_grad_norm(self):
        from paddle_tpu.nn.utils import clip_grad_norm_
        layer = nn.Linear(4, 3)
        x = t(rs.randn(2, 4).astype(np.float32))
        (layer(x) ** 2).sum().backward()
        params = list(layer.parameters())
        clip_grad_norm_(params, 0.1)
        total = sum(float((p.grad.numpy() ** 2).sum()) for p in params
                    if p.grad is not None) ** 0.5
        assert total <= 0.1 + 1e-5

    def test_vector_roundtrip(self):
        from paddle_tpu.nn.utils import (parameters_to_vector,
                                         vector_to_parameters)
        layer = nn.Linear(3, 2)
        params = list(layer.parameters())
        vec = parameters_to_vector(params)
        assert vec.shape == [3 * 2 + 2]
        vector_to_parameters(vec * 0 + 1, params)
        for p in params:
            assert abs(p.numpy() - 1).max() < 1e-6


class TestMiscModules:
    def test_fft_hfftn_roundtrip(self):
        x = t(rs.randn(2, 4, 6).astype(np.float32)).astype("complex64")
        a = paddle.fft.hfftn(x)
        b = paddle.fft.ihfftn(a)
        assert b.shape == [2, 4, 6]

    def test_subset_random_sampler(self):
        from paddle_tpu.io import SubsetRandomSampler
        s = SubsetRandomSampler([3, 5, 7])
        assert sorted(s) == [3, 5, 7] and len(s) == 3

    def test_bilinear_initializer_is_upsampler(self):
        init = nn.initializer.Bilinear()
        arr = np.asarray(init((1, 1, 4, 4), np.float32))[0, 0]
        # symmetric bilinear stencil, strictly positive
        np.testing.assert_allclose(arr, arr[::-1, ::-1])
        np.testing.assert_allclose(arr, arr.T)
        assert arr.min() > 0
        # odd kernel peaks at exactly 1 in the center
        odd = np.asarray(init((1, 1, 3, 3), np.float32))[0, 0]
        assert odd[1, 1] == 1.0

    def test_inference_enums(self):
        import paddle_tpu.inference as inf
        assert inf.get_num_bytes_of_data_type(inf.DataType.FLOAT32) == 4
        assert inf.get_trt_compile_version() == (0, 0, 0)
        assert inf.Tensor is inf.InferTensor

    def test_profiler_summary_view(self):
        import paddle_tpu.profiler as prof
        assert prof.SummaryView.OverView == 1

    def test_device_stubs(self):
        import paddle_tpu.device as dev
        assert dev.get_cudnn_version() is None
        assert dev.is_compiled_with_rocm() is False
        assert isinstance(dev.gpu.device_count(), int)

    def test_sysconfig_paths(self):
        import paddle_tpu.sysconfig as sc
        assert sc.get_lib().endswith("native")


class TestReviewRegressions3:
    def test_sparse_csr_reshape_slice(self):
        import paddle_tpu.sparse as sp
        d = np.zeros((2, 4), np.float32)
        d[0, 1], d[1, 2] = 3, 4
        csr = sp.to_sparse_csr(t(d))
        out = sp.reshape(csr, [1, 8])
        assert out.to_dense().shape == [1, 8]
        sl = sp.slice(csr, [1], [1], [3])
        np.testing.assert_allclose(sl.to_dense().numpy(), d[:, 1:3])

    def test_weight_norm_dim_none_scalar_norm(self):
        from paddle_tpu.nn.utils import weight_norm
        layer = nn.Linear(4, 3)
        weight_norm(layer, "weight", dim=None)
        assert tuple(layer.weight_g.shape) == (1, 1)
        layer2 = nn.Linear(4, 3)
        weight_norm(layer2, "weight", dim=-1)
        assert tuple(layer2.weight_g.shape) == (1, 3)

    def test_wmt_train_test_share_vocabulary(self, tmp_path):
        from paddle_tpu.text import WMT14
        (tmp_path / "train.txt").write_text("a b\tx y\nc d\tz w\n")
        (tmp_path / "test.txt").write_text("b a\ty x\n")
        tr = WMT14(data_file=str(tmp_path), mode="train")
        te = WMT14(data_file=str(tmp_path), mode="test")
        assert tr.get_dict("en") == te.get_dict("en")
        assert tr.get_dict("fr") == te.get_dict("fr")

    def test_graph_khop_sampler_contract(self):
        import paddle_tpu.incubate as inc
        # triangle graph in CSC
        row = t(np.array([1, 2, 0, 2, 0, 1], np.int64))
        colptr = t(np.array([0, 2, 4, 6], np.int64))
        src, dst, sample_index, nodes = inc.graph_khop_sampler(
            row, colptr, t(np.array([0], np.int64)), [2])
        assert src.shape == dst.shape            # a real edge list
        assert int(dst.numpy().max()) == 0       # all edges point at seed 0
        # local ids resolve through sample_index to global ids
        glob = sample_index.numpy()[src.numpy()]
        assert set(glob.tolist()) <= {1, 2}

    def test_shufflenet_swish_has_no_relu(self):
        import paddle_tpu.vision.models as M
        m = M.shufflenet_v2_swish(num_classes=2)
        assert sum(1 for s in m.sublayers()
                   if isinstance(s, nn.ReLU)) == 0
        assert sum(1 for s in m.sublayers()
                   if isinstance(s, nn.Swish)) > 20
