"""Vision model families, the classic ones (the others:
tests/test_cnn_mobile.py): each factory builds, and one compiled forward
gives finite logits of the right shape."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.vision import models as M


def forward_once(builder, in_shape, classes):
    # ONE compiled forward: run eagerly, every layer with a shape of
    # its own is a compile of its own (261 for densenet121, 38 s of its
    # 48) where the whole network compiles in 3
    model = paddle.jit.to_static(builder())
    model.eval()
    x = paddle.to_tensor(np.random.randn(*in_shape).astype("float32"))
    out = model(x)
    assert tuple(out.shape) == (in_shape[0], classes)
    assert np.isfinite(out.numpy()).all()


class TestVisionModels:
    @pytest.mark.parametrize("name,builder,in_shape", [
        ("lenet", lambda: M.LeNet(num_classes=10), (2, 1, 28, 28)),
        ("alexnet", lambda: M.alexnet(num_classes=7), (1, 3, 224, 224)),
        ("vgg11", lambda: M.vgg11(num_classes=7), (1, 3, 224, 224)),
        ("vgg11_bn", lambda: M.vgg11(batch_norm=True, num_classes=7),
         (1, 3, 224, 224)),
        ("squeezenet1_1", lambda: M.squeezenet1_1(num_classes=7),
         (1, 3, 224, 224)),
    ])
    def test_forward_shapes(self, name, builder, in_shape):
        forward_once(builder, in_shape, 7 if name != "lenet" else 10)

    def test_lenet_trains(self):
        import paddle_tpu.nn as nn
        import paddle_tpu.optimizer as optim
        model = M.LeNet(num_classes=4)
        opt = optim.Adam(parameters=model.parameters(), learning_rate=1e-3)
        x = paddle.to_tensor(np.random.randn(8, 1, 28, 28).astype("float32"))
        y = paddle.to_tensor(np.random.randint(0, 4, (8,)))
        lf = nn.CrossEntropyLoss()
        losses = []
        for _ in range(5):
            loss = lf(model(x), y)
            loss.backward()
            opt.step(); opt.clear_grad()
            losses.append(float(loss.numpy()))
        assert losses[-1] < losses[0]
