"""LLaMA ``generate``: KV-cached greedy against the uncached forward,
eos, the sampling modes."""
import numpy as np

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM


class TestGenerate:
    def _model(self):
        cfg = LlamaConfig(vocab_size=128, hidden_size=32,
                          intermediate_size=64, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2,
                          max_position_embeddings=64)
        m = LlamaForCausalLM(cfg)
        m.eval()
        return m

    def test_greedy_matches_full_forward(self):
        """KV-cached greedy decode must equal step-by-step argmax of the
        full (uncached) forward."""
        m = self._model()
        ids = paddle.to_tensor(
            np.random.randint(0, 128, (2, 5)).astype("int32"))
        out = m.generate(ids, max_new_tokens=4)
        assert tuple(out.shape) == (2, 9)
        # replay without cache
        cur = ids.numpy()
        for _ in range(4):
            logits = m(paddle.to_tensor(cur.astype("int32"))).numpy()
            nxt = logits[:, -1].argmax(-1).astype(cur.dtype)
            cur = np.concatenate([cur, nxt[:, None]], axis=1)
        np.testing.assert_array_equal(out.numpy(), cur)

    def test_eos_early_stop(self):
        m = self._model()
        ids = paddle.to_tensor(np.zeros((1, 3), "int32"))
        # pick the first greedy token as the "eos" so decoding stops at once
        first = int(m.generate(ids, max_new_tokens=1).numpy()[0, -1])
        out = m.generate(ids, max_new_tokens=8, eos_token_id=first)
        assert out.shape[1] == 4   # prompt + the single eos token

    def test_sampling_modes_run(self):
        m = self._model()
        ids = paddle.to_tensor(np.zeros((2, 3), "int32"))
        for kwargs in ({"do_sample": True, "temperature": 0.8},
                       {"do_sample": True, "top_k": 5},
                       {"do_sample": True, "top_k": 1},
                       {"do_sample": True, "top_p": 0.9}):
            out = m.generate(ids, max_new_tokens=3, **kwargs)
            assert tuple(out.shape) == (2, 6)
            assert (out.numpy() >= 0).all() and (out.numpy() < 128).all()
