"""Vision model families, the mobile and dense ones (the classic ones
and ``forward_once``: tests/test_cnn_classic.py)."""
import pytest

from paddle_tpu.vision import models as M
from test_cnn_classic import forward_once


class TestVisionModels:
    @pytest.mark.parametrize("name,builder,in_shape", [
        ("mobilenet_v1", lambda: M.mobilenet_v1(scale=0.25, num_classes=7),
         (1, 3, 224, 224)),
        ("mobilenet_v2", lambda: M.mobilenet_v2(scale=0.35, num_classes=7),
         (1, 3, 224, 224)),
        ("mobilenet_v3_small",
         lambda: M.mobilenet_v3_small(scale=0.5, num_classes=7),
         (1, 3, 224, 224)),
        ("shufflenet_v2", lambda: M.shufflenet_v2_x1_0(num_classes=7),
         (1, 3, 224, 224)),
        ("densenet121", lambda: M.densenet121(num_classes=7),
         (1, 3, 224, 224)),
    ])
    def test_forward_shapes(self, name, builder, in_shape):
        forward_once(builder, in_shape, 7)
