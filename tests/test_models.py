"""Model zoo tests: forward/backward shapes, loss decrease, TP parity on the
8-device mesh (the reference's small-scale convergence gates — SURVEY.md §4)."""

import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.distributed import mesh as pmesh
from paddle_tpu.models import (
    BertConfig,
    BertForQuestionAnswering,
    GPTConfig,
    GPTForCausalLM,
    LlamaConfig,
    LlamaForCausalLM,
)


@pytest.fixture(autouse=True)
def _reset_mesh():
    yield
    pmesh.set_mesh(None)


def ids(b, s, v=256):
    return paddle.to_tensor(np.random.randint(0, v, (b, s)).astype(np.int32))


class TestLeNet:
    def test_trains(self):
        from paddle_tpu.vision.models import LeNet

        paddle.seed(0)
        model = LeNet()
        opt = paddle.optimizer.Adam(learning_rate=1e-3, parameters=model.parameters())
        lossfn = nn.CrossEntropyLoss()
        rng = np.random.RandomState(0)
        templates = rng.rand(10, 1, 28, 28).astype(np.float32)

        @paddle.jit.to_static
        def step(x, y):
            loss = lossfn(model(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        losses = []
        for _ in range(15):
            y = rng.randint(0, 10, 16)
            x = templates[y] * 0.8 + rng.rand(16, 1, 28, 28).astype(np.float32) * 0.2
            losses.append(float(step(paddle.to_tensor(x), paddle.to_tensor(y.astype(np.int64))).numpy()))
        assert losses[-1] < losses[0] * 0.7


class TestResNet:
    def test_resnet18_forward_backward(self):
        from paddle_tpu.vision.models import resnet18

        model = resnet18(num_classes=10)
        x = paddle.to_tensor(np.random.rand(2, 3, 32, 32).astype(np.float32))
        out = model(x)
        assert out.shape == [2, 10]
        out.sum().backward()
        assert model.conv1.weight.grad is not None

    def test_resnet_nhwc_matches_nchw(self):
        # data_format="NHWC" (the TPU-native layout the benchmark uses) must
        # match NCHW numerically in both train (batch-stats BN) and eval
        from paddle_tpu.vision.models import resnet18

        rng = np.random.RandomState(0)
        x = rng.rand(4, 3, 64, 64).astype(np.float32)
        paddle.seed(0)
        m1 = resnet18(num_classes=10)
        paddle.seed(0)
        m2 = resnet18(num_classes=10, data_format="NHWC")
        xh = np.ascontiguousarray(np.transpose(x, (0, 2, 3, 1)))
        # eval with fresh stats: BN is a fixed affine, layout bugs (channel
        # mixups) would show as O(1) errors — tight tolerance
        m1.eval()
        m2.eval()
        o1 = m1(paddle.to_tensor(x)).numpy()
        o2 = m2(paddle.to_tensor(xh)).numpy()
        np.testing.assert_allclose(o1, o2, rtol=1e-4, atol=1e-4)
        # train-mode batch-stat BN amplifies fp32 reduction-order noise
        # through rsqrt(var+eps) on near-dead channels (random weights, few
        # elements per channel), so cross-layout agreement is inherently
        # loose here; real layout bugs still produce O(1) errors.
        m1.train()
        m2.train()
        o1 = m1(paddle.to_tensor(x)).numpy()
        o2 = m2(paddle.to_tensor(xh)).numpy()
        np.testing.assert_allclose(o1, o2, rtol=5e-2, atol=5e-2)

    def test_stem_space_to_depth_rewrite(self):
        # low-channel strided convs are rewritten via space-to-depth; the
        # rewrite must be numerically exact vs the direct conv, fwd and grad
        import jax
        import jax.numpy as jnp
        from jax import lax
        import paddle_tpu.nn.functional as F

        rng = np.random.RandomState(0)
        x = rng.rand(2, 3, 32, 32).astype(np.float32)
        w = (rng.rand(16, 3, 7, 7).astype(np.float32) - 0.5)
        plan = F._space_to_depth_plan((2, 3, 32, 32), w.shape, (2, 2), [(3, 3), (3, 3)], (1, 1), 1, "NCHW")
        assert plan is not None
        ref = lax.conv_general_dilated(
            jnp.asarray(x), jnp.asarray(w), (2, 2), [(3, 3), (3, 3)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        got = F.conv2d(paddle.to_tensor(x), paddle.to_tensor(w), stride=2, padding=3).numpy()
        np.testing.assert_allclose(got, np.asarray(ref), rtol=1e-5, atol=1e-5)

        wt = paddle.to_tensor(w)
        wt.stop_gradient = False
        F.conv2d(paddle.to_tensor(x), wt, stride=2, padding=3).sum().backward()

        def ref_loss(wj):
            return lax.conv_general_dilated(
                jnp.asarray(x), wj, (2, 2), [(3, 3), (3, 3)],
                dimension_numbers=("NCHW", "OIHW", "NCHW")).sum()

        g_ref = jax.grad(ref_loss)(jnp.asarray(w))
        np.testing.assert_allclose(wt.grad.numpy(), np.asarray(g_ref), rtol=1e-4, atol=1e-4)


class TestLlama:
    def test_loss_decreases_compiled(self):
        paddle.seed(0)
        cfg = LlamaConfig.tiny()
        model = LlamaForCausalLM(cfg)
        opt = paddle.optimizer.AdamW(learning_rate=1e-3, parameters=model.parameters())

        @paddle.jit.to_static
        def step(x, y):
            loss, _ = model(x, labels=y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        data = ids(4, 32)
        losses = [float(step(data, data).numpy()) for _ in range(20)]
        assert losses[-1] < losses[0] * 0.8

    def test_tp8_matches_single_device(self):
        # same seed → same init; TP=8 forward must equal dense forward
        paddle.seed(11)
        cfg = LlamaConfig.tiny()
        dense = LlamaForCausalLM(cfg)
        x = ids(2, 16)
        ref = dense(x).numpy()

        pmesh.build_mesh(mp=8)
        paddle.seed(11)
        cfg_tp = LlamaConfig.tiny(tensor_parallel_degree=8)
        tp = LlamaForCausalLM(cfg_tp)
        out = tp(x).numpy()
        np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-4)

    def test_tp8_training_step(self):
        pmesh.build_mesh(dp=1, mp=8)
        paddle.seed(0)
        cfg = LlamaConfig.tiny(tensor_parallel_degree=8)
        model = LlamaForCausalLM(cfg)
        opt = paddle.optimizer.AdamW(learning_rate=1e-3, parameters=model.parameters())

        @paddle.jit.to_static
        def step(x):
            loss, _ = model(x, labels=x)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        data = ids(2, 32)
        losses = [float(step(data).numpy()) for _ in range(10)]
        assert losses[-1] < losses[0]
        # weights remain sharded after updates
        w = model.llama.layers[0].mlp.gate_proj.weight
        assert w._raw.sharding.shard_shape(w._raw.shape)[1] == cfg.intermediate_size // 8

    def test_parallel_ce_tp8_matches_dense_and_stays_sharded(self):
        # vocab-parallel CE (mp_ops._c_softmax_with_cross_entropy parity):
        # 1) TP=8 loss == dense loss with identical weights
        # 2) the compiled TP step contains NO replicated [tokens, vocab]
        #    buffer — the sharded logsumexp keeps vocab mp-sharded end-to-end
        paddle.seed(7)
        cfg = LlamaConfig.tiny()
        dense = LlamaForCausalLM(cfg)
        data = ids(3, 16)  # tokens = 48, distinct from every model dim
        ref_loss, _ = dense(data, labels=data)
        ref = float(ref_loss.numpy())

        pmesh.build_mesh(mp=8)
        paddle.seed(7)
        cfg_tp = LlamaConfig.tiny(tensor_parallel_degree=8)
        tp = LlamaForCausalLM(cfg_tp)

        @paddle.jit.to_static
        def step(x):
            loss, _ = tp(x, labels=x)
            return loss

        got = float(step(data).numpy())
        assert abs(got - ref) / abs(ref) < 2e-3, (got, ref)

        text = step.lowered_text(data)
        # per-device shard of the [48, 256-vocab] logits is [48, 32]; a full
        # [48, 256] f32/bf16 buffer would mean GSPMD replicated the logits
        for bad in ("f32[48,256]", "bf16[48,256]", "f32[3,16,256]", "bf16[3,16,256]"):
            assert bad not in text, f"replicated logits buffer {bad} in TP step"
        assert "f32[48,32]" in text or "bf16[48,32]" in text

    def test_generate_compiled_decode(self):
        # the static-KV decode path must (a) compile exactly once for N
        # tokens, (b) agree with a full forward pass on the greedy argmax,
        # (c) stay at one compile across repeated generate() calls
        paddle.seed(5)
        cfg = LlamaConfig.tiny()
        model = LlamaForCausalLM(cfg)
        x = ids(2, 8)
        out = model.generate(x, max_new_tokens=6)
        assert out.shape == [2, 14]
        # one StaticFunction serves prefill+decode: exactly two traces
        # (one per token-chunk shape), then zero recompiles forever
        assert model._gen_fns["greedy"].trace_count == 2

        # greedy consistency: re-scoring the generated prefix with a plain
        # forward must reproduce the last generated token
        full = model(paddle.to_tensor(out.numpy()[:, :-1].astype(np.int32)))
        nxt = np.argmax(full.numpy()[:, -1], -1)
        np.testing.assert_array_equal(nxt, out.numpy()[:, -1])

        out2 = model.generate(x, max_new_tokens=6)
        np.testing.assert_array_equal(out.numpy(), out2.numpy())
        assert model._gen_fns["greedy"].trace_count == 2  # zero recompiles

    def test_generate(self):
        cfg = LlamaConfig.tiny()
        model = LlamaForCausalLM(cfg)
        out = model.generate(ids(2, 4), max_new_tokens=3)
        assert out.shape == [2, 7]

    def test_recompute_matches(self):
        paddle.seed(5)
        cfg = LlamaConfig.tiny()
        m1 = LlamaForCausalLM(cfg)
        x = ids(2, 16)
        loss1, _ = m1(x, labels=x)
        loss1.backward()
        g1 = m1.llama.layers[0].mlp.gate_proj.weight.grad.numpy()

        paddle.seed(5)
        cfg2 = LlamaConfig.tiny(use_recompute=True)
        m2 = LlamaForCausalLM(cfg2)
        loss2, _ = m2(x, labels=x)
        loss2.backward()
        g2 = m2.llama.layers[0].mlp.gate_proj.weight.grad.numpy()
        np.testing.assert_allclose(float(loss1.numpy()), float(loss2.numpy()), rtol=1e-5)
        np.testing.assert_allclose(g1, g2, rtol=1e-4, atol=1e-6)


class TestGPT:
    def test_hybrid_dp_tp_step(self):
        pmesh.build_mesh(dp=2, mp=4)
        paddle.seed(0)
        cfg = GPTConfig.tiny(tensor_parallel_degree=4)
        model = GPTForCausalLM(cfg)
        opt = paddle.optimizer.AdamW(learning_rate=1e-3, parameters=model.parameters())

        @paddle.jit.to_static
        def step(x):
            loss, _ = model(x, labels=x)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        data = ids(4, 32)
        losses = [float(step(data).numpy()) for _ in range(8)]
        assert losses[-1] < losses[0]

    def test_pipeline_layer(self):
        from paddle_tpu.distributed import fleet

        cfg = GPTConfig.tiny()
        from paddle_tpu.models import GPTForCausalLMPipe

        pipe = GPTForCausalLMPipe(cfg, num_stages=2)
        model = fleet.PipelineParallel(pipe, strategy=None)
        opt = paddle.optimizer.SGD(learning_rate=1e-3, parameters=pipe.parameters())
        x = ids(4, 16)
        loss = model.train_batch((x, x), opt)
        assert np.isfinite(float(loss.numpy()))


class TestBert:
    def test_qa_fine_tune_step(self):
        paddle.seed(0)
        cfg = BertConfig.tiny()
        model = BertForQuestionAnswering(cfg)
        opt = paddle.optimizer.AdamW(learning_rate=5e-4, parameters=model.parameters())

        @paddle.jit.to_static
        def step(x, sp, ep):
            loss, _, _ = model(x, start_positions=sp, end_positions=ep)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        x = ids(4, 32)
        sp = paddle.to_tensor(np.random.randint(0, 32, (4,)).astype(np.int32))
        losses = [float(step(x, sp, sp).numpy()) for _ in range(10)]
        assert losses[-1] < losses[0]

    def test_attention_mask(self):
        cfg = BertConfig.tiny()
        model = BertForQuestionAnswering(cfg)
        model.eval()
        x = ids(2, 16)
        mask = paddle.to_tensor(np.ones((2, 16), np.float32))
        s1, _ = model(x, attention_mask=mask)
        s2, _ = model(x)
        np.testing.assert_allclose(s1.numpy(), s2.numpy(), rtol=1e-4, atol=1e-5)


class TestBertPaddingMask:
    def test_masked_matches_truncated(self):
        # key-padding mask routed as SEGMENT IDS: valid rows must equal the
        # truncated (pad-free) computation exactly
        from paddle_tpu.models.bert import BertConfig, BertModel

        paddle.seed(0)
        cfg = BertConfig.tiny(hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
        m = BertModel(cfg)
        m.eval()
        rng = np.random.RandomState(0)
        ids = rng.randint(0, cfg.vocab_size, (2, 16)).astype(np.int32)
        mask = np.ones((2, 16), np.int64)
        mask[0, 10:] = 0
        seq_m, _ = m(paddle.to_tensor(ids), attention_mask=paddle.to_tensor(mask))
        seq_t, _ = m(paddle.to_tensor(ids[:1, :10]))
        np.testing.assert_allclose(
            seq_m.numpy()[0, :10], seq_t.numpy()[0], rtol=1e-4, atol=1e-5
        )

    def test_masked_uses_pallas_kernel(self):
        # with segment ids (not an additive mask) the Pallas kernel stays
        # eligible — verified via interpret mode at a 128-multiple seq
        from paddle_tpu.models.bert import BertConfig, BertModel
        from paddle_tpu.ops import flash_attention as fa

        saved = fa._FORCE_INTERPRET
        saved_logged = fa._fallback_logged
        fa._FORCE_INTERPRET = True
        fa._fallback_logged = False
        try:
            paddle.seed(0)
            cfg = BertConfig.tiny(hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
            m = BertModel(cfg)
            m.eval()
            rng = np.random.RandomState(0)
            ids = rng.randint(0, cfg.vocab_size, (1, 128)).astype(np.int32)
            mask = np.ones((1, 128), np.int64)
            mask[0, 100:] = 0
            seq_m, _ = m(paddle.to_tensor(ids), attention_mask=paddle.to_tensor(mask))
            assert not fa._fallback_logged, "segment-id path fell back to XLA"
            fa._FORCE_INTERPRET = saved
            seq_t, _ = m(paddle.to_tensor(ids[:, :100]))
            np.testing.assert_allclose(
                seq_m.numpy()[0, :100], seq_t.numpy()[0], rtol=1e-3, atol=1e-4
            )
        finally:
            fa._FORCE_INTERPRET = saved
            fa._fallback_logged = saved_logged


class TestGPTDecode:
    def test_generate_compiled_decode(self):
        paddle.seed(2)
        cfg = GPTConfig.tiny()
        model = GPTForCausalLM(cfg)
        x = ids(2, 8)
        out = model.generate(x, max_new_tokens=5)
        assert out.shape == [2, 13]
        assert model._gen_fns["greedy"].trace_count == 2
        full = model(paddle.to_tensor(out.numpy()[:, :-1].astype(np.int32)))
        np.testing.assert_array_equal(
            np.argmax(full.numpy()[:, -1], -1), out.numpy()[:, -1]
        )
        out2 = model.generate(x, max_new_tokens=5)
        np.testing.assert_array_equal(out.numpy(), out2.numpy())
        assert model._gen_fns["greedy"].trace_count == 2


class TestSampling:
    def test_top_k_top_p_filtering(self):
        from paddle_tpu.models._utils import _filter_logits

        lg = paddle.to_tensor(np.array([[1.0, 3.0, 2.0, -1.0, 0.5]], np.float32))
        fk = _filter_logits(lg, top_k=2, top_p=1.0).numpy()
        assert (fk > -1e29).sum() == 2
        assert fk[0, 1] == 3.0 and fk[0, 2] == 2.0
        fp = _filter_logits(lg, top_k=0, top_p=0.95).numpy()
        assert (fp > -1e29).sum() >= 1  # the top token always survives

    def test_generate_with_sampling_args(self):
        paddle.seed(1)
        cfg = LlamaConfig.tiny()
        model = LlamaForCausalLM(cfg)
        x = ids(2, 8)
        out = model.generate(x, max_new_tokens=4, temperature=0.8, top_k=5, top_p=0.9)
        assert out.shape == [2, 12]
        assert (out.numpy()[:, :8] == x.numpy()).all()
